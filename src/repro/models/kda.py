"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692 §3): the
projections with their short convolutions, the gates, the chunked prefill
and the one-token recurrent step.

Per head, with key and value width ``D`` (``KDAConfig.head_dim``), q and k
L2-normalised and q scaled by D^-1/2, a float32 state S in R^{D x D}:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
    o_t = S_t^T q_t,

with b_t = sigmoid(W_b x_t) one scalar per head and a per-channel decay
log a_t = -exp(A_log) * softplus(W_f_up W_f_down x_t + dt_bias) <= 0.
q, k and v are SiLU(causal depthwise conv(W x)), each with its own
convolution; the layer's output is W_o[sigmoid(W_g_up W_g_down x_t + bias)
* RMSNorm_head(o_t)].

Prefill (``chunk_kda``) works in chunks of C positions.  Inside a chunk,
with c_t = sum_{s<=t} log a_s the cumulative decay from the chunk's start
and u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t), the recurrence unrolls to

    S_t = Diag(e^{c_t}) S_0 + sum_{s<=t} Diag(e^{c_t - c_s}) k_s u_s^T,

and the u of the chunk solve one unit lower-triangular system (the WY/UT
transform): (I + Diag(b) M) U = Diag(b) (V - (K * e^c) S_0), with
M_ts = sum_i k_ti e^{c_ti - c_si} k_si for s < t.  Its solution is
U = W_v - W_k S_0, where W_v and W_k do not depend on S_0, so they are
computed for every chunk at once; only U, the output
O = (Q * e^c) S_0 + P U (P as M with q_t on the left, s <= t) and the
carried state S_C = Diag(e^{c_C}) S_0 + (K * e^{c_C - c})^T U run chunk
after chunk.

Stability.  The per-channel decay is not bounded below: a single position
may decay a channel by e^-11 (A = 16), and the cumulative decay of a chunk
can pass e^-700, far beyond float32.  The usual factorisation
e^{c_t - c_s} = e^{c_t} e^{-c_s}, which turns M into one matrix product,
then overflows in e^{-c_s}.  Here no exponent is ever positive:

- e^{c_t} (queries and keys against S_0) and e^{c_C - c_s} (keys into the
  carried state) have c_t <= 0 and c_C <= c_s;
- for s and t in different sub-blocks of ``SUB`` positions, with r the
  position just before t's sub-block (s <= r < t), M_ts is a matrix product
  of k_t e^{c_t - c_r} and k_s e^{c_r - c_s}, both factors in [0, 1];
- within a sub-block, e^{c_t - c_s} (s <= t) is formed pair by pair.

So every factor lies in [0, 1]: a term underflows only where its exact
value is below float32's range, and nothing overflows, whatever the decay.
The triangular system is solved by substitution, never by a power series
of M (whose partial sums cancel catastrophically when b_t k_t^T k_s is
near 1).  All of it runs in float32 with float32 matrix products.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.layers import ParamSpec
from repro.models.ssm import _causal_conv

CHUNK = 64                    # prefill chunk (positions)
SUB = 16                      # sub-block of a chunk (positions)
HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32


def kda_specs(cfg: ArchConfig) -> Dict[str, Any]:
    k, d = cfg.kda, cfg.d_model
    hk, r = k.n_heads * k.head_dim, k.head_dim     # gates' low rank

    def a_init(key, shape):            # A = U(1, 16), as Mamba draws it
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))

    def proj(n_in, n_out, init, axes=("embed", "heads")):
        return {"kernel": ParamSpec((n_in, n_out), axes, init)}

    return {
        "wq": proj(d, hk, "scaled"), "wk": proj(d, hk, "scaled"),
        "wv": proj(d, hk, "scaled"),
        "conv_q": ParamSpec((k.d_conv, hk), (None, "heads"), "scaled"),
        "conv_k": ParamSpec((k.d_conv, hk), (None, "heads"), "scaled"),
        "conv_v": ParamSpec((k.d_conv, hk), (None, "heads"), "scaled"),
        "wb": proj(d, k.n_heads, "normal"),
        "wf_a": proj(d, r, "normal", ("embed", None)),
        "wf_b": proj(r, hk, "normal", (None, "heads")),
        "A_log": ParamSpec((k.n_heads,), ("heads",), init_fn=a_init),
        "dt_bias": ParamSpec((hk,), ("heads",), "zeros"),
        "wg_a": proj(d, r, "normal", ("embed", None)),
        "wg_b": dict(proj(r, hk, "normal", (None, "heads")),
                     bias=ParamSpec((hk,), ("heads",), "zeros")),
        "o_norm": {"scale": ParamSpec((k.head_dim,), (None,), "ones")},
        "wo": proj(hk, d, "scaled", ("heads", "embed")),
    }


def conv_dim(cfg: ArchConfig) -> int:
    """Channels of the convolution state: q, k and v side by side."""
    return 3 * cfg.kda.n_heads * cfg.kda.head_dim


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def project(p, cfg: ArchConfig, x: jax.Array, dtype,
            conv_state: Optional[jax.Array] = None):
    """x: [B,S,d] (normed) -> q, k [B,S,H,D] (normalised, q scaled), v
    [B,S,H,D], the log decay g [B,S,H,D] and b [B,S,H], all float32, and
    the convolution state [B, d_conv - 1, 3HD] after the last position."""
    k = cfg.kda
    b, s, _ = x.shape
    h, dh = k.n_heads, k.head_dim
    qkv = jnp.concatenate([L.dense(p[w], x, dtype)
                           for w in ("wq", "wk", "wv")], -1)
    w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], -1)
    qkv, conv = _causal_conv(qkv, w.astype(dtype), conv_state)
    q, kk, v = (t.astype(f32).reshape(b, s, h, dh)
                for t in jnp.split(qkv, 3, -1))
    q = l2norm(q) * (1.0 / math.sqrt(dh))
    kk = l2norm(kk)
    f = L.dense(p["wf_b"], L.dense(p["wf_a"], x, dtype), f32)
    f = f.reshape(b, s, h, dh) + p["dt_bias"].astype(f32).reshape(h, dh)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(L.dense(p["wb"], x, f32))
    return q, kk, v, g, beta, conv


def output(p, cfg: ArchConfig, o: jax.Array, x: jax.Array, dtype):
    """o: [B,S,H,D] float32, x: [B,S,d] (normed) -> [B,S,d]."""
    b, s, h, dh = o.shape
    gate = L.dense(p["wg_b"], L.dense(p["wg_a"], x, dtype), f32)
    y = L.rmsnorm(p["o_norm"], o, cfg.norm_eps)
    y = y * jax.nn.sigmoid(gate.reshape(b, s, h, dh))
    return L.dense(p["wo"], y.reshape(b, s, h * dh).astype(dtype), dtype)


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _pairs(left, k, c, start):
    """sum_i left_ti e^{c_ti - c_si} k_si over the chunk's positions, for
    s < t's sub-block by the product through the sub-block's start and
    for s <= t inside it pair by pair (zero for s > t).
    left, k, c: [..., C, D]; start: [..., m, D], c just before each
    sub-block.  Returns [..., C, C]."""
    *lead, n, dk = k.shape
    m = n // SUB
    cs = c.reshape(*lead, m, SUB, dk)
    ls = left.reshape(*lead, m, SUB, dk) * jnp.exp(cs - start[..., None, :])
    before = jnp.arange(n)[None, :] < SUB * jnp.arange(m)[:, None]  # [m,C]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], start[..., :, None, :] - c[..., None, :, :],
        -jnp.inf))                                         # [..., m, C, D]
    off = _mm("...mtd,...msd->...mts", ls, right)
    ks = k.reshape(*lead, m, SUB, dk)
    tri = jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :]
    e = jnp.exp(jnp.where(tri[..., None],
                          cs[..., :, None, :] - cs[..., None, :, :],
                          -jnp.inf))                       # [..., m, t, s, D]
    diag = jnp.sum(left.reshape(*lead, m, SUB, dk)[..., :, None, :] * e
                   * ks[..., None, :, :], -1)              # [..., m, t, s]
    diag = jnp.einsum("...its,ij->...itjs", diag, jnp.eye(m, dtype=f32))
    return (off + diag.reshape(off.shape)).reshape(*lead, n, n)


def chunk_kda(q, k, v, g, beta, chunk: int,
              state: Optional[jax.Array] = None):
    """The recurrence over a whole sequence, ``chunk`` positions at a time
    (see the module's notes).  q, k, g: [B,S,H,D]; v: [B,S,H,V]; beta:
    [B,S,H]; state: [B,H,D,V] before the first position (zeros if None).
    Returns (o [B,S,H,V], the state after the last position), float32."""
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {SUB}")
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    n = -(-s // chunk)
    pad = n * chunk - s
    if pad:        # k = v = b = 0 and no decay: the state passes unchanged
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))

    def chunks(t):                                   # [B,S,H,*] -> [B,N,H,C,*]
        return jnp.moveaxis(t.reshape(b, n, chunk, h, *t.shape[3:]), 3, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-2)                       # [B,N,H,C,D], <= 0
    sub_end = c.reshape(*c.shape[:-2], chunk // SUB, SUB, dk)[..., -1, :]
    start = jnp.concatenate([jnp.zeros_like(sub_end[..., :1, :]),
                             sub_end[..., :-1, :]], -2)
    mk = _pairs(k, k, c, start)
    pq = _pairs(q, k, c, start)
    strict = jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :]
    a = jnp.where(strict, beta[..., :, None] * mk, 0.0)
    decay = jnp.exp(c)
    rhs = beta[..., None] * jnp.concatenate([v, k * decay], -1)
    w = jax.scipy.linalg.solve_triangular(a, rhs, lower=True,
                                          unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]
    qd = q * decay
    last = c[..., -1:, :]
    kd = k * jnp.exp(last - c)
    carry = jnp.exp(last[..., 0, :])                 # [B,N,H,D]

    def step(st, xs):
        w_v, w_k, qd, p, kd, carry = xs
        u = w_v - _mm("bhcd,bhdv->bhcv", w_k, st)
        o = _mm("bhcd,bhdv->bhcv", qd, st) + _mm("bhcs,bhsv->bhcv", p, u)
        st = carry[..., None] * st + _mm("bhcd,bhcv->bhdv", kd, u)
        return st, o

    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (w_v, w_k, qd, pq, kd, carry))
    state, o = jax.lax.scan(step, state, xs)         # o: [N,B,H,C,V]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, n * chunk, h, dv)
    return o[:, :s], state


def kda_step(q, k, v, g, beta, state):
    """One position.  q, k, g: [B,H,D]; v: [B,H,V]; beta: [B,H]; state:
    [B,H,D,V].  Returns (o [B,H,V], the new state).  The state is read
    twice (its products with a*k and a*q, then the update) and written
    once, with no matrix product: the work is the state's bytes."""
    a = jnp.exp(g)
    r = jnp.sum(state * (a * k)[..., None], -2)      # (Diag(a) S)^T k
    sq = jnp.sum(state * (a * q)[..., None], -2)     # (Diag(a) S)^T q
    u = beta[..., None] * (v - r)
    new = state * a[..., None] + k[..., None] * u[..., None, :]
    o = sq + jnp.sum(k * q, -1, keepdims=True) * u
    return o, new


def kda_prefill(p, cfg: ArchConfig, x: jax.Array, dtype):
    """x: [B,S,d] (normed) -> (y [B,S,d], state [B,H,D,D], conv state)."""
    q, k, v, g, beta, conv = project(p, cfg, x, dtype)
    o, state = chunk_kda(q, k, v, g, beta, CHUNK)
    return output(p, cfg, o, x, dtype), state, conv


def kda_decode(p, cfg: ArchConfig, x: jax.Array, state: jax.Array,
               conv: jax.Array, dtype):
    """x: [B,1,d] (normed); state [B,H,D,D]; conv [B, d_conv - 1, 3HD] ->
    (y [B,1,d], the new state, the new conv state)."""
    q, k, v, g, beta, conv = project(p, cfg, x, dtype, conv)
    o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state)
    return output(p, cfg, o[:, None], x, dtype), state, conv
