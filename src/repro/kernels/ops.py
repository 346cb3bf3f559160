"""jit'd wrappers assembling full operations from the Pallas kernels.

The kernels compile with Mosaic on a TPU backend and run in Pallas
interpret mode on any other backend: the choice follows
``jax.default_backend()``, so a TPU run never falls back to the
interpreter.

``ssd`` composes the intra-chunk kernel with the cheap inter-chunk
recurrence (lax.scan) and the C·h_in inter-chunk output term.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.ssd import ssd_intra


def interpret_mode() -> bool:
    """Interpret the kernels everywhere but on a TPU backend."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, group: int,
                    causal: bool = True, window=None, cap: float = 0.0,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """Model-facing signature (positions are arange; rope pre-applied)."""
    return _flash(q, k, v, group=group, causal=causal, window=window,
                  cap=cap, bq=bq, bk=bk, interpret=interpret_mode())


def flash_decode(q, k_cache, v_cache, lengths, *, group: int, window=None,
                 cap: float = 0.0, bk: int = 256) -> jax.Array:
    return _flash_decode(q, k_cache, v_cache, lengths, group=group,
                         window=window, cap=cap, bk=bk,
                         interpret=interpret_mode())


def ssd(xh, dt, A, Bp, Cp, *, chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Full SSD layer: Pallas intra-chunk + lax.scan inter-chunk.
    Returns (y [B,S,nh,hp] f32, h_final [B,nh,hp,N] f32)."""
    b, s, nh, hp = xh.shape
    n = Bp.shape[-1]
    q = min(chunk, s)
    nc = -(-s // q)
    y_intra, s_chunk, dec, cum = ssd_intra(xh, dt, A, Bp, Cp, chunk,
                                           interpret=interpret_mode())
    pad = nc * q - s
    Cq = (jnp.pad(Cp, ((0, 0), (0, pad), (0, 0))) if pad else Cp) \
        .astype(jnp.float32).reshape(b, nc, q, n)

    def chunk_step(h, xs):
        s_c, dec_c, c_c, cum_c = xs
        # inter-chunk output: C_t · h_in * exp(cum_t)
        y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp", c_c, h, jnp.exp(cum_c))
        h = dec_c[:, :, None, None] * h + s_c
        return h, y_inter

    h0 = jnp.zeros((b, nh, hp, n), jnp.float32)
    xs = (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(dec, 1, 0),
          jnp.moveaxis(Cq, 1, 0), jnp.moveaxis(cum, 1, 0))
    h_fin, y_inter = jax.lax.scan(chunk_step, h0, xs)
    y_inter = jnp.moveaxis(y_inter, 0, 1).reshape(b, nc * q, nh, hp)[:, :s]
    return y_intra[:, :s] + y_inter, h_fin
