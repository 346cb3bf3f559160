"""GQA attention: reference (quadratic), chunked (streaming softmax), pallas;
and multi-head latent attention (MLA), decompressed for prefill and
absorbed for decode.

TPU-mesh head padding
---------------------
The production mesh has a 16-way ``model`` axis, but several assigned archs
have head counts not divisible by 16 (llama4/qwen2.5: 40 q heads, 8 kv heads).
JAX rejects uneven input shardings, so the parameter layout pads q heads up to
a multiple of the TP size (pad heads are zero-init and **masked out of the
output**, keeping the math of the assigned arch exact) and expands kv heads by
replication slots (Megatron-style replicated KV for tp > n_kv_heads).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.layers import ParamSpec

# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadLayout:
    n_heads: int          # real q heads
    n_kv: int             # real kv heads
    h_pad: int            # padded q slots (divisible by tp)
    kv_pad: int           # padded kv slots (divisible by tp, divides h_pad)
    repeat: int           # kv replication factor kv_pad / n_kv
    head_dim: int

    @staticmethod
    def make(a: AttnConfig, tp: int) -> "HeadLayout":
        h, kv = a.n_heads, a.n_kv_heads
        assert h % kv == 0, (h, kv)
        # smallest integer replication r with tp | kv*r (exact kv copies)
        r = tp // math.gcd(kv, tp)
        kv_pad = kv * r
        lcm = tp * kv_pad // math.gcd(tp, kv_pad)
        h_pad = lcm * math.ceil(max(h, 1) / lcm)
        return HeadLayout(h, kv, h_pad, kv_pad, r, a.head_dim)

    @property
    def group(self) -> int:            # q slots per kv slot
        return self.h_pad // self.kv_pad

    @property
    def g_real(self) -> int:           # q slots per REAL kv head
        return self.h_pad // self.n_kv

    def head_mask(self) -> np.ndarray:
        """[h_pad] 1.0 for real q heads, 0.0 for structural padding."""
        real_per_group = self.n_heads // self.n_kv
        s = np.arange(self.h_pad)
        return ((s % self.g_real) < real_per_group).astype(np.float32)

    @property
    def n_pad(self) -> int:
        return self.h_pad - self.n_heads


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_specs(a: AttnConfig, d: int, layout: HeadLayout) -> Dict[str, Any]:
    hd = a.head_dim
    kv_axes = (("embed", "kv_heads", "head_dim") if layout.repeat == 1
               else ("embed", None, None))
    mask = layout.head_mask()

    def q_init(key, shape):
        w = 0.02 * jax.random.normal(key, shape)
        return w * mask[None, :, None]          # zero the pad-head columns

    specs: Dict[str, Any] = {
        "wq": {"kernel": ParamSpec((d, layout.h_pad, hd),
                                   ("embed", "heads", "head_dim"),
                                   init_fn=q_init)},
        "wk": {"kernel": ParamSpec((d, layout.n_kv, hd), kv_axes, "scaled")},
        "wv": {"kernel": ParamSpec((d, layout.n_kv, hd), kv_axes, "scaled")},
        "wo": {"kernel": ParamSpec((layout.h_pad, hd, d),
                                   ("heads", "head_dim", "embed"), "scaled")},
    }
    if a.qkv_bias:
        specs["wq"]["bias"] = ParamSpec((layout.h_pad, hd),
                                        ("heads", "head_dim"), "zeros")
        specs["wk"]["bias"] = ParamSpec((layout.n_kv, hd),
                                        (kv_axes[1], kv_axes[2]), "zeros")
        specs["wv"]["bias"] = ParamSpec((layout.n_kv, hd),
                                        (kv_axes[1], kv_axes[2]), "zeros")
    if a.qk_norm:
        specs["q_norm"] = {"scale": ParamSpec((hd,), (None,), "ones")}
        specs["k_norm"] = {"scale": ParamSpec((hd,), (None,), "ones")}
    return specs


def _proj(p, x, heads_axes, dtype):
    y = jnp.einsum("bsd,dhk->bshk", x.astype(dtype), p["kernel"].astype(dtype))
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    return shard(y, *heads_axes)


def qkv(params, a: AttnConfig, layout: HeadLayout, x: jax.Array,
        positions: jax.Array, dtype, *, rope: bool = True,
        kv_x: Optional[jax.Array] = None, kv_positions=None):
    """Project to padded-slot q and kv-slot k/v, applying qk-norm + RoPE."""
    kv_x = x if kv_x is None else kv_x
    q = _proj(params["wq"], x, ("batch", "seq", "act_heads", None), dtype)
    k = _proj(params["wk"], kv_x, ("batch", "seq", None, None), dtype)
    v = _proj(params["wv"], kv_x, ("batch", "seq", None, None), dtype)
    if a.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    if rope:
        q = L.apply_rope(q, positions, a.rope_theta)
        kpos = positions if kv_positions is None else kv_positions
        k = L.apply_rope(k, kpos, a.rope_theta)
    if layout.repeat > 1:
        k = jnp.repeat(k, layout.repeat, axis=2)
        v = jnp.repeat(v, layout.repeat, axis=2)
    k = shard(k, "batch", "kv_seq", "act_heads", None)
    v = shard(v, "batch", "kv_seq", "act_heads", None)
    return q, k, v


def out_proj(params, layout: HeadLayout, ctx: jax.Array, dtype) -> jax.Array:
    mask = jnp.asarray(layout.head_mask(), dtype)
    ctx = ctx * mask[None, None, :, None]        # kill structural pad heads
    y = jnp.einsum("bshk,hkd->bsd", ctx.astype(dtype),
                   params["wo"]["kernel"].astype(dtype))
    return shard(y, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, window, causal: bool):
    """Additive mask bias [..., Sq, Sk].  window: traced int32, <0 = global."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    win_ok = (window < 0) | (d < window)
    ok &= win_ok
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core attention impls (q: [B,Sq,Hp,hd], k/v: [B,Sk,KVp,hd])
# ---------------------------------------------------------------------------


def _gqa_scores(q, k, group: int):
    """-> [B, KVp, G, Sq, Sk] in f32."""
    b, sq, hp, hd = q.shape
    qg = q.reshape(b, sq, hp // group, group, hd)
    return jnp.einsum("bsngk,btnk->bngst", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) / math.sqrt(hd)


def _gqa_out(probs, v, hp: int):
    b, n, g, sq, sk = probs.shape
    ctx = jnp.einsum("bngst,btnk->bsngk", probs, v.astype(jnp.float32))
    return ctx.reshape(b, sq, hp, v.shape[-1])


def attend_reference(q, k, v, q_pos, k_pos, layout: HeadLayout, *,
                     causal: bool, window, cap: float = 0.0,
                     kv_len=None) -> jax.Array:
    scores = _gqa_scores(q, k, layout.group)
    scores = L.softcap(scores, cap)
    bias = _mask_bias(q_pos, k_pos, window, causal)
    if kv_len is not None:                       # decode: mask empty cache slots
        bias = bias + jnp.where(k_pos < kv_len, 0.0, -1e30)[..., None, :]
    scores = scores + bias[:, None, None] if bias.ndim == 3 else scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v, layout.h_pad).astype(q.dtype)


def attend_chunked(q, k, v, q_pos, k_pos, layout: HeadLayout, *,
                   causal: bool, window, cap: float = 0.0,
                   q_chunk: int = 1024, kv_chunk: int = 1024,
                   causal_skip: bool = False) -> jax.Array:
    """Streaming-softmax (flash-style) attention in pure lax.  Exact.

    Scans q in blocks; for each q block scans kv blocks carrying running
    (max, denom, acc).  ``causal_skip`` unrolls the q loop and truncates each
    inner scan at the causal frontier (§Perf lever: removes the ~2× masked
    FLOPs of the dense schedule).
    """
    b, sq, hp, hd = q.shape
    sk, hv = k.shape[1], v.shape[-1]            # hv: value head width
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    nq, nk = -(-sq // qc), -(-sk // kc)
    pad_q, pad_k = nq * qc - sq, nk * kc - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)), constant_values=2 ** 30)

    g = layout.group
    n = hp // g
    kb = k.reshape(b, nk, kc, n, hd)
    vb = v.reshape(b, nk, kc, n, hv)
    kpb = k_pos.reshape(b, nk, kc)

    def q_block(qi, kv_hi):
        qs = q[:, qi * qc:(qi + 1) * qc]
        qp = q_pos[:, qi * qc:(qi + 1) * qc]
        qg = qs.reshape(b, qc, n, g, hd).astype(jnp.float32)

        def kv_step(carry, xs):
            m, l, acc = carry
            kj, vj, kpj = xs                        # [b,kc,n,hd],[b,kc]
            s = jnp.einsum("bsngk,btnk->bngst", qg,
                           kj.astype(jnp.float32)) / math.sqrt(hd)
            s = L.softcap(s, cap)
            s = s + _mask_bias(qp, kpj, window, causal)[:, None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l = l * scale + jnp.sum(p, axis=-1)
            acc = acc * scale[..., None] + jnp.einsum(
                "bngst,btnk->bngsk", p, vj.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((b, n, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, n, g, qc), jnp.float32)
        a0 = jnp.zeros((b, n, g, qc, hv), jnp.float32)
        xs = (jnp.moveaxis(kb, 1, 0)[:kv_hi], jnp.moveaxis(vb, 1, 0)[:kv_hi],
              jnp.moveaxis(kpb, 1, 0)[:kv_hi])
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l[..., None])                  # [b,n,g,qc,hd]
        return jnp.moveaxis(out, 3, 1).reshape(b, qc, hp, hv)

    if causal_skip and causal:
        # unrolled q loop; inner scan only over kv blocks at/below the diagonal
        outs = [q_block(i, min(nk, (((i + 1) * qc - 1) // kc) + 1))
                for i in range(nq)]
    else:
        outs = [q_block(i, nk) for i in range(nq)]
    out = jnp.concatenate(outs, axis=1)[:, :sq]
    return out.astype(q.dtype)


def attend_decode(q, k_cache, v_cache, cache_len, layout: HeadLayout, *,
                  window, cap: float = 0.0) -> jax.Array:
    """Single-token decode over a (possibly seq-sharded) KV cache.

    q: [B,1,Hp,hd]; caches: [B,S,KVp,hd].  A plain masked softmax over the
    cache: under a seq-sharded cache GSPMD partitions the reductions into
    flash-decode-style partials + tiny all-reduces (LSE combine).
    """
    b, s, kvp, hd = k_cache.shape
    k_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    cur = (cache_len[:, None] if cache_len.ndim == 1 else cache_len) - 1
    scores = _gqa_scores(q, k_cache, layout.group)       # [B,KVp,G,1,S]
    scores = L.softcap(scores, cap)
    d = cur[..., :, None] - k_pos[..., None, :]          # [B,1,S]; cur = query pos
    ok = (d >= 0) & ((window < 0) | (d < window))        # d>=0 excludes empty slots
    bias = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    scores = scores + bias[:, None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v_cache, layout.h_pad).astype(q.dtype)


def attend(impl: str, q, k, v, q_pos, k_pos, layout, *, causal, window,
           cap=0.0, q_chunk=1024, kv_chunk=1024, causal_skip=False):
    if impl == "reference":
        return attend_reference(q, k, v, q_pos, k_pos, layout,
                                causal=causal, window=window, cap=cap)
    if impl == "chunked":
        return attend_chunked(q, k, v, q_pos, k_pos, layout, causal=causal,
                              window=window, cap=cap, q_chunk=q_chunk,
                              kv_chunk=kv_chunk, causal_skip=causal_skip)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, q_pos, k_pos,
                                    group=layout.group, causal=causal,
                                    window=window, cap=cap)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 §2.1.1)
# ---------------------------------------------------------------------------
#
# q is a plain projection (no q latent) to per-head (nope | rope) parts;
# ``wkv_a`` gives the latent c_kv, RMS-normed, and one rotary key k_pe shared
# by all heads; ``wkv_b`` expands c_kv to per-head k_nope and v.  The cache
# holds c_kv and k_pe only.  Prefill attends in the decompressed form;
# decode folds ``wkv_b``'s key half into the query and its value half after
# the weighted sum, so it reads the latent cache as it is.


def mla_specs(a: AttnConfig, d: int) -> Dict[str, Any]:
    m, h = a.mla, a.n_heads
    return {
        "wq": {"kernel": ParamSpec((d, h, a.head_dim + m.rope_dim),
                                   ("embed", "heads", None), "normal")},
        "wkv_a": {"kernel": ParamSpec((d, m.kv_lora_rank + m.rope_dim),
                                      ("embed", None), "scaled")},
        "kv_norm": {"scale": ParamSpec((m.kv_lora_rank,), (None,), "ones")},
        "wkv_b": {"kernel": ParamSpec(
            (m.kv_lora_rank, h, a.head_dim + m.v_head_dim),
            (None, "heads", None), "scaled")},
        "wo": {"kernel": ParamSpec((h, m.v_head_dim, d),
                                   ("heads", None, "embed"), "scaled")},
    }


def mla_project(params, a: AttnConfig, x: jax.Array, positions: jax.Array,
                dtype):
    """x: [B,S,d] -> q_nope [B,S,H,nope], q_pe [B,S,H,rope], c_kv [B,S,rank]
    (normed) and k_pe [B,S,rope]; RoPE (interleaved pairs) on the rope
    parts, unless ``a.mla.rope`` is off (NoPE: they stay as projected)."""
    m = a.mla
    x = x.astype(dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"]["kernel"].astype(dtype))
    q_pe = q[..., a.head_dim:]
    if m.rope:
        q_pe = L.apply_rope_interleaved(q_pe, positions, a.rope_theta)
    kv = x @ params["wkv_a"]["kernel"].astype(dtype)
    c_kv = L.rmsnorm(params["kv_norm"], kv[..., :m.kv_lora_rank])
    k_pe = kv[..., None, m.kv_lora_rank:]
    if m.rope:
        k_pe = L.apply_rope_interleaved(k_pe, positions, a.rope_theta)
    k_pe = k_pe[..., 0, :]
    return q[..., :a.head_dim], q_pe, c_kv, k_pe


def mla_out(params, ctx: jax.Array, dtype) -> jax.Array:
    """ctx: [B,S,H,v] -> [B,S,d]."""
    y = jnp.einsum("bshk,hkd->bsd", ctx.astype(dtype),
                   params["wo"]["kernel"].astype(dtype))
    return shard(y, "batch", "seq", "act_embed")


def mla_prefill(params, a: AttnConfig, layout: HeadLayout, q_nope, q_pe,
                c_kv, k_pe, positions, dtype, *, impl: str = "chunked",
                chunk: int = 1024) -> jax.Array:
    """Causal attention in the decompressed form: per-head keys
    ``[k_nope | k_pe]`` and values from ``wkv_b``; scale 1/sqrt(nope+rope).
    Returns the heads' context [B,S,H,v]."""
    if impl == "pallas":
        raise NotImplementedError("MLA has no Pallas attention kernel")
    kv = jnp.einsum("bsc,chk->bshk", c_kv.astype(dtype),
                    params["wkv_b"]["kernel"].astype(dtype))
    k_nope, v = kv[..., :a.head_dim], kv[..., a.head_dim:]
    b, s, h, _ = q_nope.shape
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None].astype(dtype),
                                  (b, s, h, a.mla.rope_dim))], axis=-1)
    return attend(impl, q, k, v, positions, positions, layout, causal=True,
                  window=jnp.int32(-1), q_chunk=chunk, kv_chunk=chunk)


def mla_decode(params, a: AttnConfig, q_nope, q_pe, c_cache, pe_cache,
               cache_len, dtype) -> jax.Array:
    """One query per row against the latent cache, absorbed: q_nope is
    taken into the latent space by ``wkv_b``'s key half, scores are read
    against c_kv and k_pe as cached, and the weighted sum of c_kv goes
    through ``wkv_b``'s value half.  q_*: [B,1,H,*]; c_cache [B,S,rank],
    pe_cache [B,S,rope]; positions below ``cache_len`` [B] are attended.
    Returns the heads' context [B,1,H,v]."""
    # float32 products, as attend_decode computes them (on the TPU, XLA
    # feeds the cache's bf16 to the MXU as it is: no widened copy)
    f32 = jnp.float32
    w = params["wkv_b"]["kernel"].astype(dtype).astype(f32)
    w_uk, w_uv = w[..., :a.head_dim], w[..., a.head_dim:]
    c_cache = c_cache.astype(f32)
    q_lat = jnp.einsum("bshk,chk->bshc", q_nope.astype(f32), w_uk)
    scores = (jnp.einsum("bshc,btc->bhst", q_lat, c_cache)
              + jnp.einsum("bshr,btr->bhst", q_pe.astype(f32),
                           pe_cache.astype(f32)))
    scores = scores / math.sqrt(a.head_dim + a.mla.rope_dim)
    t = jnp.arange(c_cache.shape[1], dtype=jnp.int32)
    ok = t[None, :] < cache_len[:, None]                      # [B,S]
    scores = jnp.where(ok[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btc->bshc", probs, c_cache)
    return jnp.einsum("bshc,chk->bshk", ctx, w_uv).astype(dtype)
