"""Pallas TPU flash-decode: single-token attention over a long KV cache,
partitioned over kv blocks with online-softmax (LSE) combination — the
kernel twin of the seq-sharded decode softmax the SPMD partitioner builds
for ``long_500k`` (DESIGN.md).

The wrapper lays q out as [B,KV,G,hd] and the caches as [B,KV,S,hd], so
one grid step scores the G query heads of a kv group against a (bk, hd)
cache block.  Grid (B, KV, nK), kv innermost; per-row cache lengths and
the window are scalar-prefetch operands (SMEM); scratch carries (m, l, acc)
per (b, kv group).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import window_operand

NEG_INF = -1e30


def _decode_kernel(len_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bk: int, n_kv: int,
                   cap: float, scale: float):
    i_kv = pl.program_id(2)

    @pl.when(i_kv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # [G, hd]
    k = k_ref[...].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)                    # [G, bk]

    cur = len_ref[pl.program_id(0)] - 1                # query position
    k_pos = i_kv * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    d = cur - k_pos
    win = win_ref[0]
    ok = (d >= 0) & ((win < 0) | (d < win))
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                                # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(i_kv == n_kv - 1)
    def _write():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, group: int,
                 window: Optional[jax.Array] = None, cap: float = 0.0,
                 bk: int = 256, interpret: bool) -> jax.Array:
    """q: [B,1,H,hd]; caches: [B,S,KV,hd]; lengths: [B] (valid entries incl.
    the current token)."""
    b, _, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    bk = min(bk, s)
    n_k = -(-s // bk)
    qg = q.reshape(b, kv, group, hd)                   # head h = kv*G + g
    k_cache, v_cache = (jnp.swapaxes(c, 1, 2) for c in (k_cache, v_cache))
    if n_k * bk - s:
        pad = ((0, 0), (0, 0), (0, n_k * bk - s), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)

    kernel = functools.partial(_decode_kernel, bk=bk, n_kv=n_k, cap=cap,
                               scale=1.0 / math.sqrt(hd))
    q_spec = pl.BlockSpec((None, None, group, hd),
                          lambda bb, kh, ik, lens, win: (bb, kh, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda bb, kh, ik, lens, win: (bb, kh, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, n_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), window_operand(window), qg, k_cache,
      v_cache)
    return out.reshape(b, 1, h, hd)
