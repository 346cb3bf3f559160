"""Pallas TPU Mamba2/SSD intra-chunk kernel.

The SSD layer splits into (a) an O(q^2) *intra-chunk* part (attention-like
masked-decay matmuls — the MXU hot spot) and (b) an O(nchunk) sequential
state recurrence.  The kernel computes, per (batch, head, chunk):

    y_intra = (L ∘ (C B^T)) Xdt          [q, hp]
    s_chunk = (decay_out ∘ Xdt)^T B      [hp, N] contribution to the state

The wrapper puts heads before sequence (x: [B,nh,S,hp], dt: [B,nh,S,1]) so
every block ends in an (8,128)-tileable pair; A sits in SMEM.  The
within-chunk cumulative log decay is a masked reduction over a [q,q]
tile (Mosaic has no cumsum).  The cheap inter-chunk recurrence + C·h_in
inter term run as a lax.scan in ``ops.ssd`` — this mirrors how the CUDA
SSD kernel is adapted to the TPU's (MXU + sequential-grid) execution model
(DESIGN.md §2 hardware adaptation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *, q: int):
    x = x_ref[...].astype(jnp.float32)                 # [q, hp]
    dt = dt_ref[...].astype(jnp.float32)               # [q, 1]
    A = a_ref[pl.program_id(1)]                        # scalar (<0)
    B = b_ref[...].astype(jnp.float32)                 # [q, N]
    C = c_ref[...].astype(jnp.float32)                 # [q, N]

    la = dt * A                                        # log decay per step
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    cum_r = jnp.sum(jnp.where(row <= col, la, 0.0), axis=0,
                    keepdims=True)                     # [1, q] cumsum
    cum_c = jnp.sum(jnp.where(row == col, cum_r, 0.0), axis=1,
                    keepdims=True)                     # [q, 1] same, as column
    xdt = x * dt

    Lk = jnp.exp(jnp.where(row >= col, cum_c - cum_r, -jnp.inf))
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [q,q]
    y_ref[...] = jax.lax.dot_general(
        Lk * cb, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    total = jnp.sum(la, axis=0, keepdims=True)         # [1, 1]
    decay_out = jnp.exp(total - cum_c)                 # [q, 1]
    s_ref[...] = jax.lax.dot_general(
        xdt * decay_out, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)   # [hp, N]


def ssd_intra(xh: jax.Array, dt: jax.Array, A: jax.Array, Bp: jax.Array,
              Cp: jax.Array, chunk: int, *, interpret: bool):
    """xh: [B,S,nh,hp]; dt: [B,S,nh] f32; A: [nh]; Bp/Cp: [B,S,N].
    Returns (y_intra [B,S,nh,hp] f32, s_chunk [B,nc,nh,hp,N] f32,
    decay [B,nc,nh] f32, cum [B,nc,q,nh])."""
    b, s, nh, hp = xh.shape
    n = Bp.shape[-1]
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(Bp, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(Cp, ((0, 0), (0, pad), (0, 0)))
    s_pad = nc * q

    kernel = functools.partial(_ssd_kernel, q=q)
    head_seq = lambda bb, hh, cc: (bb, hh, cc, 0)      # noqa: E731
    seq = lambda bb, hh, cc: (bb, cc, 0)               # noqa: E731
    y, s_chunk = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, q, hp), head_seq),
            pl.BlockSpec((None, None, q, 1), head_seq),
            pl.BlockSpec((None, q, n), seq),
            pl.BlockSpec((None, q, n), seq),
        ],
        out_specs=[
            pl.BlockSpec((None, None, q, hp), head_seq),
            pl.BlockSpec((None, None, None, hp, n),
                         lambda bb, hh, cc: (bb, hh, cc, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s_pad, hp), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, nc, hp, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(A.astype(jnp.float32), jnp.swapaxes(xh, 1, 2),
      jnp.swapaxes(dt, 1, 2)[..., None], Bp, Cp)
    # cum is recomputed cheaply outside for the inter-chunk term and the
    # per-chunk total decay
    la = (dt * A[None, None, :]).reshape(b, nc, q, nh)
    cum = jnp.cumsum(la, axis=2)
    dec = jnp.exp(cum[:, :, -1])
    return (jnp.swapaxes(y, 1, 2), jnp.moveaxis(s_chunk, 2, 1), dec, cum)
