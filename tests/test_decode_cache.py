"""Attention decode writes its stacked KV cache in place.

``lm_decode`` carries the whole cache through the layer scan and writes
each row's new entry at [layer, row, length].  The reference below is the
form it replaced, which scanned each layer's slice as the scan's xs and
returned the updated slice as its ys: the two must give the same logits
and the same cache, bit for bit, and nothing off [layer, row, length] may
change."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import attention as A
from repro.models import layers as L
from repro.models.model_zoo import build_model
from repro.models.transformer import (
    _aux_zero, _mlp_block, embed_tokens, unembed,
)

MAX_SEQ = 16
LENGTHS = (0, 5, MAX_SEQ - 1, 9)       # empty, middle, last position


def _xs_ys_decode(params, cfg, dims, token, cache):
    """The per-layer xs/ys decode of the dense, moe and vlm families."""
    plus_one = cfg.name.startswith("gemma")
    lengths = cache["length"]
    positions = lengths[:, None]
    rows = jnp.arange(token.shape[0])
    x = embed_tokens(params, cfg, dims, token)
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)

    def body(carry, xs):
        xc, aux = carry
        p, win, k_l, v_l = xs
        aux = dict(aux)
        h = L.rmsnorm(p["attn_norm"], xc, cfg.norm_eps, plus_one=plus_one)
        dt = xc.dtype
        q, k, v = A.qkv(p["attn"], cfg.attn, dims.layout, h, positions, dt)
        k_l = k_l.at[rows, lengths].set(k[:, 0].astype(k_l.dtype))
        v_l = v_l.at[rows, lengths].set(v[:, 0].astype(v_l.dtype))
        ctx = A.attend_decode(q, k_l, v_l, lengths + 1, dims.layout,
                              window=win, cap=cfg.attn.softcap)
        attn_out = A.out_proj(p["attn"], dims.layout, ctx, dt)
        xc = _mlp_block(p, cfg, xc + attn_out, plus_one=plus_one, aux=aux)
        return (xc, aux), (k_l, v_l)

    (x, aux), (k_all, v_all) = jax.lax.scan(
        body, (x, _aux_zero(cfg)),
        (params["layers"], windows, cache["k"], cache["v"]))
    cache = dict(cache, length=lengths + 1, k=k_all, v=v_all)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps, plus_one=plus_one)
    return unembed(params, cfg, dims, x), cache, aux


def _cfg(case):
    if case == "sliding_window":
        cfg = reduced(get_config("gemma3-4b"))
        # layer 0 sees the last 4 positions, layer 1 all of them
        return dataclasses.replace(cfg, attn=dataclasses.replace(
            cfg.attn, local_window=4, global_every=2))
    if case == "moe":
        cfg = reduced(get_config("olmoe-1b-7b"))
        return dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
    if case == "qkv_bias":
        # biased q/k/v projections and an untied head
        return reduced(get_config("qwen2.5-14b"))
    return reduced(get_config("qwen3-1.7b"))


def _with_biases(params, key):
    """Every bias (initialised to zero) drawn at random, so that it counts."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree.unflatten(tree, [
        0.1 * jax.random.normal(jax.random.fold_in(key, j), a.shape, a.dtype)
        if path[-1].key == "bias" else a
        for j, (path, a) in enumerate(flat)])


def _filled_cache(m, key):
    """A cache whose every entry holds a value, rows at ``LENGTHS``."""
    cache = m.init_cache(len(LENGTHS), MAX_SEQ)
    for j, name in enumerate(sorted(k for k in cache if k != "length")):
        a = cache[name]
        r = jax.random.normal(jax.random.fold_in(key, j), a.shape)
        cache[name] = r.astype(a.dtype)
    cache["length"] = jnp.asarray(LENGTHS, jnp.int32)
    return cache


@pytest.mark.parametrize("case", ["dense", "sliding_window", "moe",
                                  "qkv_bias"])
def test_in_place_decode_equals_xs_ys_decode(case, rng_key):
    cfg = _cfg(case)
    m = build_model(cfg)
    params = _with_biases(m.init(rng_key), jax.random.PRNGKey(6))
    cache = _filled_cache(m, jax.random.PRNGKey(7))
    token = jax.random.randint(jax.random.PRNGKey(8), (len(LENGTHS), 1), 0,
                               cfg.vocab_size)
    want_lg, want, want_aux = jax.jit(
        lambda p, t, c: _xs_ys_decode(p, cfg, m.dims, t, c))(
            params, token, cache)
    got_lg, got, got_aux = jax.jit(m.decode_step)(params, token, cache)

    np.testing.assert_array_equal(np.asarray(got_lg), np.asarray(want_lg))
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)
    for name in want_aux:
        np.testing.assert_array_equal(np.asarray(got_aux[name]),
                                      np.asarray(want_aux[name]))

    # only [layer, row, length] is written, and it is written
    rows = np.arange(len(LENGTHS))
    for name in ("k", "v"):
        before, after = np.asarray(cache[name]), np.asarray(got[name])
        written = np.zeros(before.shape[:3], bool)
        written[:, rows, np.asarray(LENGTHS)] = True
        np.testing.assert_array_equal(after[~written], before[~written])
        assert (after[written] != before[written]).any(), name
