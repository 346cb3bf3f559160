"""The Pallas kernels compile for a described TPU v5e (no chip needed) at
the widths the chip smoke runs: qwen3-1.7b attention (16 query / 8 kv
heads of 128, sequence 2048, decode cache 4096) and mamba2-780m SSD (48
heads of 64, state 128, chunk 256).  What Mosaic refuses here it would
refuse on the chip.  The batched decode and the slot insert, compiled as
the serving engine compiles them (cache donated), write the cache in
place: no cache-sized temporary, no copy of the stacked cache.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file."""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd import ssd_intra
from repro.models.model_zoo import build_model
from repro.models.ssm import ssm_dims
from repro.serve.engine import insert_slot

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


# the cache arrays a decode reads whole
_STACKED = ("k", "v", "c_kv", "k_pe")
# case -> (config, decode slots, positions a slot holds): two layers at the
# published widths.  ``bf16_cache`` is the qwen3-1.7b serving cell's decode
# batch, ``steady_shape`` the steady cell's (1824 positions, not a multiple
# of 128); ``mla_moe`` is the control, whose decode always carried its
# cache: Moonlight's leading dense layer and one expert layer.
_DECODE = {
    "bf16_cache": ("qwen3-1.7b", 32, 768),
    "steady_shape": ("qwen3-1.7b", 16, 1824),
    "mla_moe": ("moonlight-16b-a3b", 32, 768),
}


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _assert_in_place(compiled, cache, slots, max_seq):
    # under one layer's k slice of qwen3's bf16 cache (50.3 MB at 32 x 768)
    layer_slice = slots * max_seq * _KV * _HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice
    # no copy in device memory (HBM) of a whole cache array; one into the
    # core's fast memory (layout tagged S(1)) stages a slice
    text = compiled.as_text()
    for name in [n for n in _STACKED if n in cache]:
        a = cache[name]
        shape = f"bf16[{','.join(map(str, a.shape))}]"
        copies = [layout for layout in re.findall(
            re.escape(shape) + r"\{([^}]*)\} copy\(", text)
            if "S(" not in layout]
        assert not copies, (name, copies)


@pytest.mark.parametrize("case", sorted(_DECODE))
def test_decode_writes_cache_in_place_for_v5e(case, one_chip,
                                              no_persistent_cache):
    arch, slots, max_seq = _DECODE[case]
    m = build_model(dataclasses.replace(get_config(arch), n_layers=2))
    params = _on_chip(jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))),
                      one_chip)
    cache = _on_chip(jax.eval_shape(lambda: m.init_cache(slots, max_seq)),
                     one_chip)
    token = jax.ShapeDtypeStruct((slots, 1), i32, sharding=one_chip)
    compiled = jax.jit(m.decode_step, donate_argnums=(2,)).lower(
        params, token, cache).compile()
    _assert_in_place(compiled, cache, slots, max_seq)


@pytest.mark.parametrize("case", sorted(_DECODE))
def test_insert_writes_cache_in_place_for_v5e(case, one_chip,
                                              no_persistent_cache):
    """The slot insert, jitted as ``ServeEngine`` jits it (decode cache and
    ``last_token`` donated, the slot a traced index), writes the prefilled
    row into the decode cache without copying it."""
    arch, slots, max_seq = _DECODE[case]
    m = build_model(dataclasses.replace(get_config(arch), n_layers=2))
    cache = _on_chip(jax.eval_shape(lambda: m.init_cache(slots, max_seq)),
                     one_chip)
    row = _on_chip(jax.eval_shape(lambda: m.init_cache(1, max_seq)),
                   one_chip)
    scalar, last, tok = (jax.ShapeDtypeStruct(s, i32, sharding=one_chip)
                         for s in ((), (slots, 1), (1, 1)))
    compiled = jax.jit(insert_slot, donate_argnums=(0, 3)).lower(
        cache, row, scalar, last, tok).compile()
    _assert_in_place(compiled, cache, slots, max_seq)
    # the new cache is the donated one
    size = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= size
