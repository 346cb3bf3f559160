"""Pallas TPU flash attention (GQA, causal, sliding-window, soft-cap).

The wrapper puts heads before sequence ([B,H,S,hd]) so every block ends in
an (8,128)-tileable ``(bq, hd)`` pair.  Grid (B, H, nQ, nK); the kv
dimension is innermost ("arbitrary") so the online-softmax state
(m, l, acc) lives in VMEM scratch across kv blocks.  GQA is expressed in
the BlockSpec index maps (q head h reads kv head h//g) — no materialized
KV repetition.  The window is a scalar-prefetch operand (SMEM).  Block
shapes default to (128, 128): MXU-aligned tiles; VMEM working set per
step = bq*hd + bk*hd (q,k,v tiles) + bq*(hd+2) f32 scratch ≈ 0.2 MB at
hd=128.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def window_operand(window) -> jax.Array:
    """The window as the [1] int32 scalar-prefetch operand (-1 = global)."""
    if isinstance(window, jax.Array):
        return window.astype(jnp.int32).reshape(1)
    return jnp.asarray([-1 if window is None else window], jnp.int32)


def _flash_kernel(win_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bq: int, bk: int, n_kv: int,
                  kv_len: int, causal: bool, cap: float, scale: float):
    i_q = pl.program_id(2)
    i_kv = pl.program_id(3)

    @pl.when(i_kv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)               # [bq, hd]
    k = k_ref[...].astype(jnp.float32)               # [bk, hd]
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)

    q_pos = i_q * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = i_kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    d = q_pos - k_pos
    ok = k_pos < kv_len                  # mask padded keys
    if causal:
        ok &= d >= 0
    win = win_ref[0]
    ok &= (win < 0) | (d < win)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                               # [bq, 1]
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    group: int, causal: bool = True,
                    window: Optional[jax.Array] = None,
                    cap: float = 0.0, bq: int = 128, bk: int = 128,
                    interpret: bool) -> jax.Array:
    """q: [B,S,H,hd]; k/v: [B,S,KV,hd] with H = KV*group.  Positions are
    arange (rope applied by the caller)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    assert h == kv * group
    bq = min(bq, s)
    bk = min(bk, s)
    n_q = -(-s // bq)
    n_k = -(-s // bk)
    # heads before sequence: blocks end in (bq|bk, hd)
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if n_q * bq - s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, n_q * bq - s), (0, 0)))
    if n_k * bk - s:
        pad = ((0, 0), (0, 0), (0, n_k * bk - s), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_k, kv_len=s, causal=causal,
        cap=cap, scale=1.0 / math.sqrt(hd))
    q_spec = pl.BlockSpec((None, None, bq, hd),
                          lambda bb, hh, iq, ik, win: (bb, hh, iq, 0))
    kv_spec = pl.BlockSpec(
        (None, None, bk, hd),
        lambda bb, hh, iq, ik, win: (bb, hh // group, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, n_q, n_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, n_q * bq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(window_operand(window), q, k, v)
    return jnp.swapaxes(out[:, :, :s], 1, 2)
