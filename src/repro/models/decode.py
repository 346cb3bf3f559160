"""Prefill and single-token decode over stacked KV / SSM caches.

Attention decode carries the stacked cache through the layer scan and writes
each row's new entry in place at [layer, row, length], so no layer's slice is
copied out and back; cache writes are per-row scatters so continuous batching
(per-row lengths) works.  Prefill runs the full-sequence layer stack once and
writes every layer's keys and values (or latent entries) into the cache.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import kvcache as KC
from repro.models.transformer import (
    ModelDims, _aux_zero, _hybrid_groups, _mlp_block, _shared_attn_block,
    embed_tokens, unembed,
)


def _split_conv(cfg: ArchConfig, conv: jax.Array):
    d_inner, _ = S.ssm_dims(cfg)
    n = cfg.ssm.d_state
    return (conv[..., :d_inner], conv[..., d_inner:d_inner + n],
            conv[..., d_inner + n:])


def _merge_conv(parts) -> jax.Array:
    return jnp.concatenate(parts, axis=-1)


def _write_kv(k_c, v_c, k_new, v_new, lengths, layer=None):
    """Per-row scatter write of one token's kv at each row's length, into
    one layer's cache [B,S,KVp,hd] or, given ``layer``, in place into the
    stacked cache [L,B,S,KVp,hd]."""
    at = (() if layer is None else (layer,)) + (
        jnp.arange(k_new.shape[0]), lengths)
    k_c = k_c.at[at].set(k_new[:, 0].astype(k_c.dtype))
    v_c = v_c.at[at].set(v_new[:, 0].astype(v_c.dtype))
    axes = KC.CACHE_AXES["k"][-k_c.ndim:]
    return shard(k_c, *axes), shard(v_c, *axes)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def lm_prefill(params, cfg: ArchConfig, dims: ModelDims, tokens,
               cache: Dict[str, Any], *, patch_embeds=None
               ) -> Tuple[jax.Array, Dict[str, Any], Dict]:
    """Fill the cache from a full prompt; returns last-position logits."""
    from repro.models.transformer import decoder_stack
    plus_one = cfg.name.startswith("gemma")
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed_tokens(params, cfg, dims, tokens, patch_embeds)

    if cfg.family == "mla_moe":
        x, aux, (c_kv, k_pe) = decoder_stack(params, cfg, dims, x, positions,
                                             collect_kv=True)
        for key, val in (("c_kv", c_kv), ("k_pe", k_pe)):  # [L,B,s,*]
            cache[key] = jax.lax.dynamic_update_slice(
                cache[key], val.astype(cache[key].dtype), (0, 0, 0, 0))
    elif cfg.family in ("dense", "moe", "vlm"):
        x, aux, kv = decoder_stack(params, cfg, dims, x, positions,
                                   collect_kv=True, plus_one=plus_one)
        k, v = kv                                   # [L,B,s,KVp,hd]
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    elif cfg.family == "ssm":
        x, aux = _ssm_prefill(params, cfg, x, cache)
    elif cfg.family == "hybrid":
        x, aux = _hybrid_prefill(params, cfg, dims, x, positions, cache)
    else:
        raise ValueError(cfg.family)

    cache["length"] = jnp.full_like(cache["length"], s)
    cache = KC.shard_cache(cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps, plus_one=plus_one)
    logits = unembed(params, cfg, dims, x[:, -1:])
    return logits, cache, aux


def _ssm_prefill(params, cfg, x, cache):
    def body(carry, p):
        xc = carry
        h = L.rmsnorm(p["ssm_norm"], xc, cfg.norm_eps)
        dtype = h.dtype
        z, xh, Bp, Cp, dt, conv_st = S._project(p["ssm"], cfg, h, dtype)
        Aa = -jnp.exp(p["ssm"]["A_log"].astype(jnp.float32))
        y, h_fin = S.ssd_chunked(xh, dt, Aa, Bp, Cp, cfg.ssm.chunk)
        out = S._finish(p["ssm"], cfg, y, xh, dt, z, dtype)
        return xc + out, (h_fin, _merge_conv(conv_st))
    (x), (h_all, conv_all) = jax.lax.scan(body, x, params["layers"])
    cache["ssm"] = h_all
    cache["conv"] = conv_all.astype(cache["conv"].dtype)
    return x, _aux_zero(cfg)


def _hybrid_prefill(params, cfg, dims, x, positions, cache):
    ae, n_groups, rem = _hybrid_groups(cfg)

    def body(carry, p):
        xc = carry
        h = L.rmsnorm(p["ssm_norm"], xc, cfg.norm_eps)
        dtype = h.dtype
        z, xh, Bp, Cp, dt, conv_st = S._project(p["ssm"], cfg, h, dtype)
        Aa = -jnp.exp(p["ssm"]["A_log"].astype(jnp.float32))
        y, h_fin = S.ssd_chunked(xh, dt, Aa, Bp, Cp, cfg.ssm.chunk)
        out = S._finish(p["ssm"], cfg, y, xh, dt, z, dtype)
        return xc + out, (h_fin, _merge_conv(conv_st))

    h_states, conv_states, kvs = [], [], []
    for g in range(n_groups):
        sl = jax.tree.map(lambda a: a[g * ae:(g + 1) * ae], params["layers"])
        x, (hs, cs) = jax.lax.scan(body, x, sl)
        h_states.append(hs); conv_states.append(cs)
        x, kv = _shared_attn_block(params, cfg, dims, x, positions,
                                   collect_kv=True)
        kvs.append(kv)
    if rem:
        sl = jax.tree.map(lambda a: a[n_groups * ae:], params["layers"])
        x, (hs, cs) = jax.lax.scan(body, x, sl)
        h_states.append(hs); conv_states.append(cs)

    cache["ssm"] = jnp.concatenate(h_states, axis=0)
    cache["conv"] = jnp.concatenate(conv_states, axis=0).astype(cache["conv"].dtype)
    k = jnp.stack([kv[0] for kv in kvs])
    v = jnp.stack([kv[1] for kv in kvs])
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    return x, _aux_zero(cfg)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def lm_decode(params, cfg: ArchConfig, dims: ModelDims, token,
              cache: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any], Dict]:
    """One decode step.  token: [B,1] int32.  Returns (logits, cache, aux)."""
    plus_one = cfg.name.startswith("gemma")
    lengths = cache["length"]                        # [B]
    positions = lengths[:, None]
    x = embed_tokens(params, cfg, dims, token)
    windows = jnp.asarray(cfg.layer_windows() or [0], jnp.int32)
    aux = _aux_zero(cfg)

    if cfg.family == "mla_moe":
        x, aux = _mla_moe_decode(params, cfg, x, positions, lengths, cache,
                                 aux)
    elif cfg.family in ("dense", "moe", "vlm"):
        def body(carry, xs):
            xc, aux, k_all, v_all = carry
            p, win, i = xs
            aux = dict(aux)
            h = L.rmsnorm(p["attn_norm"], xc, cfg.norm_eps, plus_one=plus_one)
            dt = xc.dtype
            q, k, v = A.qkv(p["attn"], cfg.attn, dims.layout, h, positions, dt)
            k_all, v_all = _write_kv(k_all, v_all, k, v, lengths, i)
            ctx = A.attend_decode(q, k_all[i], v_all[i], lengths + 1,
                                  dims.layout, window=win,
                                  cap=cfg.attn.softcap)
            xc = xc + A.out_proj(p["attn"], dims.layout, ctx, dt)
            xc = _mlp_block(p, cfg, xc, plus_one=plus_one, aux=aux)
            return (xc, aux, k_all, v_all), None

        (x, aux, cache["k"], cache["v"]), _ = jax.lax.scan(
            body, (x, aux, cache["k"], cache["v"]),
            (params["layers"], windows, jnp.arange(cfg.n_layers)))

    elif cfg.family == "ssm":
        def body(carry, xs):
            xc = carry
            p, h_l, conv_l = xs
            h = L.rmsnorm(p["ssm_norm"], xc, cfg.norm_eps)
            out, h_new, conv_new = S.mamba2_decode(
                p["ssm"], cfg, h, h_l, _split_conv(cfg, conv_l))
            return xc + out, (h_new, _merge_conv(conv_new).astype(conv_l.dtype))
        x, (h_all, conv_all) = jax.lax.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv"]))
        cache["ssm"], cache["conv"] = h_all, conv_all

    elif cfg.family == "hybrid":
        ae, n_groups, rem = _hybrid_groups(cfg)

        def body(carry, xs):
            xc = carry
            p, h_l, conv_l = xs
            h = L.rmsnorm(p["ssm_norm"], xc, cfg.norm_eps)
            out, h_new, conv_new = S.mamba2_decode(
                p["ssm"], cfg, h, h_l, _split_conv(cfg, conv_l))
            return xc + out, (h_new, _merge_conv(conv_new).astype(conv_l.dtype))

        h_states, conv_states, k_all, v_all = [], [], [], []
        for g in range(n_groups):
            sl = jax.tree.map(lambda a: a[g * ae:(g + 1) * ae],
                              params["layers"])
            hs = cache["ssm"][g * ae:(g + 1) * ae]
            cs = cache["conv"][g * ae:(g + 1) * ae]
            x, (hn, cn) = jax.lax.scan(body, x, (sl, hs, cs))
            h_states.append(hn); conv_states.append(cn)
            k_l, v_l = cache["k"][g], cache["v"][g]
            p_sh = params["shared_attn"]
            hh = L.rmsnorm(p_sh["norm"], x, cfg.norm_eps)
            q, k, v = A.qkv(p_sh["attn"], cfg.attn, dims.layout, hh,
                            positions, x.dtype)
            k_l, v_l = _write_kv(k_l, v_l, k, v, lengths)
            ctx = A.attend_decode(q, k_l, v_l, lengths + 1, dims.layout,
                                  window=jnp.int32(-1))
            x = x + A.out_proj(p_sh["attn"], dims.layout, ctx, x.dtype)
            hh = L.rmsnorm(p_sh["mlp_norm"], x, cfg.norm_eps)
            x = x + L.mlp(p_sh["mlp"], hh, cfg.act, x.dtype)
            k_all.append(k_l); v_all.append(v_l)
        if rem:
            sl = jax.tree.map(lambda a: a[n_groups * ae:], params["layers"])
            hs = cache["ssm"][n_groups * ae:]
            cs = cache["conv"][n_groups * ae:]
            x, (hn, cn) = jax.lax.scan(body, x, (sl, hs, cs))
            h_states.append(hn); conv_states.append(cn)
        cache["ssm"] = jnp.concatenate(h_states, axis=0)
        cache["conv"] = jnp.concatenate(conv_states, axis=0)
        cache["k"] = jnp.stack(k_all)
        cache["v"] = jnp.stack(v_all)
    else:
        raise ValueError(cfg.family)

    cache["length"] = lengths + 1
    cache = KC.shard_cache(cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps, plus_one=plus_one)
    logits = unembed(params, cfg, dims, x)
    return logits, cache, aux


def _mla_moe_decode(params, cfg: ArchConfig, x, positions, lengths,
                    cache: Dict[str, Any], aux: Dict):
    """One token per row through the dense then the expert layers, attention
    absorbed over the latent cache.  The whole cache rides in the scans'
    carry: each layer writes its row entries and reads its own slice, so no
    layer's cache is copied out and back."""
    rows = jnp.arange(x.shape[0])
    dt = x.dtype

    def body(carry, xs):
        xc, aux, c_all, pe_all = carry
        p, i = xs
        aux = dict(aux)
        with jax.named_scope("nugget_block_mla"):
            h = L.rmsnorm(p["attn_norm"], xc, cfg.norm_eps)
            q_nope, q_pe, c, pe = A.mla_project(p["attn"], cfg.attn, h,
                                                positions, dt)
            c_all = c_all.at[i, rows, lengths].set(c[:, 0].astype(
                c_all.dtype))
            pe_all = pe_all.at[i, rows, lengths].set(pe[:, 0].astype(
                pe_all.dtype))
            ctx = A.mla_decode(p["attn"], cfg.attn, q_nope, q_pe, c_all[i],
                               pe_all[i], lengths + 1, dt)
            xc = xc + A.mla_out(p["attn"], ctx, dt)
        xc = _mlp_block(p, cfg, xc, plus_one=False, aux=aux)
        return (xc, aux, c_all, pe_all), None

    nd = cfg.first_k_dense
    carry = (x, aux, cache["c_kv"], cache["k_pe"])
    carry, _ = jax.lax.scan(body, carry, (params["dense_layers"],
                                          jnp.arange(nd)))
    carry, _ = jax.lax.scan(body, carry, (params["layers"],
                                          jnp.arange(nd, cfg.n_layers)))
    x, aux, cache["c_kv"], cache["k_pe"] = carry
    return x, aux
