"""The Pallas kernels compile for a described TPU v5e (no chip needed) at
the widths the chip smoke runs: qwen3-1.7b attention (16 query / 8 kv
heads of 128, sequence 2048, decode cache 4096) and mamba2-780m SSD (48
heads of 64, state 128, chunk 256).  What Mosaic refuses here it would
refuse on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd import ssd_intra
from repro.models.ssm import ssm_dims

_QWEN3 = get_config("qwen3-1.7b").attn
_MAMBA2 = get_config("mamba2-780m")
_H, _KV, _HD = _QWEN3.n_heads, _QWEN3.n_kv_heads, _QWEN3.head_dim
_G = _H // _KV
_NH = ssm_dims(_MAMBA2)[1]
_SSM = _MAMBA2.ssm
_S, _DEC_B, _DEC_S = 2048, 4, 4096
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

CASES = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, group=_G, interpret=False),
        [((1, _S, _H, _HD), bf16), ((1, _S, _KV, _HD), bf16),
         ((1, _S, _KV, _HD), bf16)]),
    "flash_decode": (
        lambda q, k, v, n: flash_decode(q, k, v, n, group=_G,
                                        interpret=False),
        [((_DEC_B, 1, _H, _HD), bf16), ((_DEC_B, _DEC_S, _KV, _HD), bf16),
         ((_DEC_B, _DEC_S, _KV, _HD), bf16), ((_DEC_B,), i32)]),
    "ssd": (
        lambda x, dt, a, b, c: ssd_intra(x, dt, a, b, c, _SSM.chunk,
                                         interpret=False),
        [((1, _S, _NH, _SSM.head_dim), f32), ((1, _S, _NH), f32),
         ((_NH,), f32), ((1, _S, _SSM.d_state), f32),
         ((1, _S, _SSM.d_state), f32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
