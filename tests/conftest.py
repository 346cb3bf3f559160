import os
import signal
import sys
import threading

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (tests that need more set it in a child).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))   # for the _hyp shim

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")

# pytest-timeout-style per-test cap without the plugin: set
# REPRO_TEST_TIMEOUT=<seconds> (CI does) to fail any single test that
# hangs past the cap instead of stalling the whole job.
_TEST_TIMEOUT_S = int(os.environ.get("REPRO_TEST_TIMEOUT", "0"))


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    if (_TEST_TIMEOUT_S <= 0
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _alarm(signum, frame):
        pytest.fail(f"test exceeded REPRO_TEST_TIMEOUT={_TEST_TIMEOUT_S}s",
                    pytrace=False)

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
