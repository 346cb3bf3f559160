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
from repro.models import kvcache as KC
from repro.models import layers as L
from repro.models import moe as M
from repro.models.model_zoo import build_model
from repro.models.transformer import (
    _aux_zero, _mlp_block, embed_tokens, unembed,
)

MAX_SEQ = 16
LENGTHS = (0, 5, MAX_SEQ - 1, 9)       # empty, middle, last position


def _xs_ys_decode(params, cfg, dims, token, cache):
    """The per-layer xs/ys decode of the dense, moe and vlm families."""
    plus_one = cfg.name.startswith("gemma")
    quant = cfg.cache_quant == "int8"
    lengths = cache["length"]
    positions = lengths[:, None]
    rows = jnp.arange(token.shape[0])
    x = embed_tokens(params, cfg, dims, token)
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)

    def body(carry, xs):
        xc, aux = carry
        p, win, *kv = xs
        aux = dict(aux)
        h = L.rmsnorm(p["attn_norm"], xc, cfg.norm_eps, plus_one=plus_one)
        dt = xc.dtype
        q, k, v = A.qkv(p["attn"], cfg.attn, dims.layout, h, positions, dt)
        if quant:
            k_l, v_l, ks_l, vs_l = kv
            kq, ks = KC.quantize_kv(k[:, 0])
            vq, vs = KC.quantize_kv(v[:, 0])
            kv = [k_l.at[rows, lengths].set(kq),
                  v_l.at[rows, lengths].set(vq),
                  ks_l.at[rows, lengths].set(ks),
                  vs_l.at[rows, lengths].set(vs)]
            k_at = KC.dequantize_kv(kv[0], kv[2], dt)
            v_at = KC.dequantize_kv(kv[1], kv[3], dt)
        else:
            k_l, v_l = kv
            kv = [k_l.at[rows, lengths].set(k[:, 0].astype(k_l.dtype)),
                  v_l.at[rows, lengths].set(v[:, 0].astype(v_l.dtype))]
            k_at, v_at = kv
        ctx = A.attend_decode(q, k_at, v_at, lengths + 1, dims.layout,
                              window=win, cap=cfg.attn.softcap)
        attn_out = A.out_proj(p["attn"], dims.layout, ctx, dt)
        if cfg.parallel_block:
            h2 = L.rmsnorm(p["mlp_norm"], xc, cfg.norm_eps, plus_one=plus_one)
            if "moe" in p:
                y, moe_aux = M.moe_mlp(p["moe"], cfg, h2)
                for key, val in moe_aux.items():
                    aux[key] = aux.get(key, 0) + val
            else:
                y = L.mlp(p["mlp"], h2, cfg.act, dt)
            xc = xc + (attn_out + y)
        else:
            xc = _mlp_block(p, cfg, xc + attn_out, plus_one=plus_one, aux=aux)
        return (xc, aux), tuple(kv)

    keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    (x, aux), kv = jax.lax.scan(
        body, (x, _aux_zero(cfg)),
        (params["layers"], windows, *(cache[key] for key in keys)))
    cache = dict(cache, length=lengths + 1, **dict(zip(keys, kv)))
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
    cfg = reduced(get_config("qwen3-1.7b"))
    if case == "int8_cache":
        return dataclasses.replace(cfg, cache_quant="int8")
    if case == "parallel_block":
        return dataclasses.replace(cfg, parallel_block=True)
    return cfg


def _filled_cache(m, key):
    """A cache whose every entry holds a value, rows at ``LENGTHS``."""
    cache = m.init_cache(len(LENGTHS), MAX_SEQ)
    for j, name in enumerate(sorted(k for k in cache if k != "length")):
        a = cache[name]
        r = jax.random.normal(jax.random.fold_in(key, j), a.shape)
        if a.dtype == jnp.int8:
            r = jnp.clip(jnp.round(r * 40), -127, 127)
        elif name.endswith("_scale"):
            r = 0.01 + jnp.abs(r) * 0.02
        cache[name] = r.astype(a.dtype)
    cache["length"] = jnp.asarray(LENGTHS, jnp.int32)
    return cache


@pytest.mark.parametrize("case", ["dense", "int8_cache", "sliding_window",
                                  "parallel_block", "moe"])
def test_in_place_decode_equals_xs_ys_decode(case, rng_key):
    cfg = _cfg(case)
    m = build_model(cfg)
    params = m.init(rng_key)
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
    for name in ("k", "v") + (("k_scale", "v_scale")
                              if cfg.cache_quant == "int8" else ()):
        before, after = np.asarray(cache[name]), np.asarray(got[name])
        written = np.zeros(before.shape[:3], bool)
        written[:, rows, np.asarray(LENGTHS)] = True
        np.testing.assert_array_equal(after[~written], before[~written])
        assert (after[written] != before[written]).any(), name
