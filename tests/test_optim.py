"""Optimizer + gradient-compression properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         dequantize, global_norm, init_opt_state,
                         quantize_int8)


def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=0)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = init_opt_state(params, cfg)
    for _ in range(200):
        g = {"w": 2 * (params["w"] - target)}
        params, state, _ = adamw_update(params, g, state, cfg,
                                        jnp.asarray(0.1))
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_grad_clip():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    g2 = {"a": jnp.full((10,), 1e-3)}
    clipped2, _ = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(np.asarray(clipped2["a"]),
                               np.asarray(g2["a"]))


def test_master_weights_bf16_params():
    cfg = AdamWConfig(lr=1e-4, use_master=True, grad_clip=0,
                      weight_decay=0.0)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = init_opt_state(params, cfg)
    # many tiny updates that would vanish in bf16 but accumulate in master
    for _ in range(50):
        g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
        params, state, _ = adamw_update(params, g, state, cfg,
                                        jnp.asarray(1e-5))
    assert float(state.master["w"][0]) != 1.0


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-4, 1e3))
def test_quantize_int8_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * scale, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6     # half-ulp of the int8 grid


def test_error_feedback_unbiased_over_time():
    """EF compression: the *accumulated* applied signal tracks the true
    accumulated gradient (bias shrinks), though each step is lossy."""
    from repro.optim.grad_compress import compress_leaf
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    ef = jnp.zeros_like(g_true)
    applied = jnp.zeros_like(g_true)
    for _ in range(60):
        q, s, ef = compress_leaf(g_true, ef)
        applied = applied + dequantize(q, s)
    # mean applied per step ≈ g_true
    np.testing.assert_allclose(np.asarray(applied) / 60, np.asarray(g_true),
                               atol=2e-2)


def test_compressed_psum_matches_sum_shardmap():
    """int8 EF psum under shard_map on 1 device == plain sum (n=1)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.optim.grad_compress import compressed_psum, init_error_feedback

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    g = {"w": jnp.linspace(-1, 1, 32)}
    ef = init_error_feedback(g)

    def f(g, ef):
        return compressed_psum(g, ef, "dp")

    out, new_ef = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                out_specs=(P(), P()))(g, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=1e-2)
