"""The serving window kind at a size a CPU run holds: a sound run is
correct, and a run with the timed path broken underneath is not."""
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import serve_cell
from bench import common
from bench.kinds import serve

SEED = 2**31 + 11


def _run(cell, seconds=2.0, trace=False):
    out = serve.run(cell, SEED, seconds, trace)
    return out, common.correct(out["checks"])


def test_sound_run_is_correct():
    out, ok = _run(serve_cell(), trace=True)
    assert ok, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["notes"]["compiles_in_window"] == 0
    assert out["notes"]["served_tokens_compared"] >= 3
    assert out["end_to_end"]["ttft_p95_ms"] > 0
    rec = out["record"]
    assert rec["prefill_s"] and rec["decode_s"] and rec["queue_wait_s"]


def test_altered_token_is_caught(monkeypatch):
    """A token altered where it is produced."""
    from repro.serve import engine

    def wrong(logits):
        return ((jnp.argmax(logits[:, -1], axis=-1) + 1) % logits.shape[-1]
                )[:, None].astype(jnp.int32)
    monkeypatch.setattr(engine, "greedy", wrong)
    out, ok = _run(serve_cell())
    assert not ok, out["checks"]


def test_unchanged_state_is_caught(monkeypatch):
    """A decode step that returns its state (the cache) unchanged."""
    from repro.models import model_zoo
    real = model_zoo.Model.decode_step

    def stale(self, params, token, cache):
        # the decode writes into the dict it is given: hand it a copy
        logits, _, aux = real(self, params, token, dict(cache))
        return logits, cache, aux
    monkeypatch.setattr(model_zoo.Model, "decode_step", stale)
    out, ok = _run(serve_cell())
    assert not ok, out["checks"]


def test_control_reads_above_the_program():
    """The reference computed in fp8, put in the program's place, reads
    more than three times the program's widest gap, and the run's own
    comparison at the cell's limit passes the program and fails it."""
    cell = serve_cell()
    import jax
    from bench import arrivals
    eng, init = serve.build(cell)
    params = init(jax.random.PRNGKey(SEED))
    sched = arrivals.schedule(cell.traffic, 2.0)
    served = serve.Served(sched, arrivals.prompts(
        cell.traffic, cell.config["vocab_size"], SEED, len(sched)))
    serve.serve(eng, params, served, 2.0)
    ids = serve.sample(served, SEED, cell.spec["sample_requests"])
    prog = serve.reference_gaps(cell, SEED, served, ids)
    ctrl = serve.reference_gaps(cell, SEED, served, ids, control=True)
    assert ctrl.max() > 3 * prog.max(), (ctrl.max(), prog.max())
    assert common.correct(serve.checks(cell, prog))
    assert not common.correct(serve.checks(cell, ctrl)), ctrl.max()


def test_sample_has_the_longest():
    s = serve.Served([(0.1 * i, 3) for i in range(6)], [None] * 6)
    s.outputs = {i: [0] * (2 + i % 4) for i in range(6)}
    ids = serve.sample(s, SEED, 3)
    assert ids[0] == 3 and len(set(ids)) == 3
    assert ids == serve.sample(s, SEED, 3)
