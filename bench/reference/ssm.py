"""Plain float32 reference of a Mamba-2 language model, written from the
paper (Dao and Gu, arXiv:2405.21060) in its recurrent form: pre-norm
residual blocks; per block the projections z, x, B, C, dt of the normed
input; a depthwise causal convolution of width ``d_conv`` with SiLU on x, B
and C; dt = softplus(dt + dt_bias) per head, A = -exp(A_log); the selective
state recurrence h_t = exp(dt_t·A)·h_{t-1} + dt_t·x_t⊗B_t, y_t = h_t·C_t
with one B/C group shared by all heads; y + D·x, gated by silu(z), then
RMSNorm and the output projection.  A final RMSNorm and the LM head tied to
the embedding.  The loss adds 1e-4·mean(lse²) (z-loss) to the
cross-entropy, as the program's training objective does.  As in the
program, there is no convolution bias (see the configuration's departures).

The recurrence runs step by step, ``SCAN_BLOCK`` steps to a recomputed
block, so that its backward pass fits one chip; ``chunk_size`` plays no part
in it.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.train import HIGHEST, Leaf, lm_loss, rmsnorm, silu

SCAN_BLOCK = 64
Z_LOSS = 1e-4


def param_specs(c: Dict):
    n, d, st, k = c["n_layer"], c["d_model"], c["d_state"], c["d_conv"]
    di = c["expand"] * d
    nh = di // c["headdim"]
    return {
        "embed": {"embedding": Leaf((c["vocab_size"], d), "normal")},
        "final_norm": {"scale": Leaf((d,), "ones")},
        "layers": {
            "ssm": {
                "A_log": Leaf((n, nh), "a_log"),
                "D": Leaf((n, nh), "ones"),
                "conv_B": Leaf((n, k, st), "scaled"),
                "conv_C": Leaf((n, k, st), "scaled"),
                "conv_x": Leaf((n, k, di), "scaled"),
                "dt_bias": Leaf((n, nh), "zeros"),
                "norm": {"scale": Leaf((n, di), "ones")},
                "wB": {"kernel": Leaf((n, d, st), "scaled")},
                "wC": {"kernel": Leaf((n, d, st), "scaled")},
                "wdt": {"kernel": Leaf((n, d, nh), "scaled")},
                "wo": {"kernel": Leaf((n, di, d), "scaled")},
                "wx": {"kernel": Leaf((n, d, di), "scaled")},
                "wz": {"kernel": Leaf((n, d, di), "scaled")},
            },
            "ssm_norm": {"scale": Leaf((n, d), "ones")},
        },
    }


def causal_conv(x, w):
    """x: [b, s, c]; w: [k, c]; y_t = sum_i w_i · x_{t-k+1+i}."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(k))


def recurrence(x, dt, a, bm, cm):
    """x: [b, s, nh, hp]; dt: [b, s, nh]; a: [nh]; bm, cm: [b, s, n]."""
    b, s, nh, hp = x.shape
    n = bm.shape[-1]

    def step(h, xs):
        xt, dtt, bt, ct = xs
        h = (jnp.exp(dtt * a)[:, :, None, None] * h
             + (xt * dtt[..., None])[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    blk = min(SCAN_BLOCK, s)

    def split(t):                        # [b, s, ...] -> [s/blk, blk, b, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(s // blk, blk, *t.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((b, nh, hp, n)),
                        (split(x), split(dt), split(bm), split(cm)))
    return jnp.moveaxis(y.reshape(s, b, nh, hp), 0, 1)


def loss(params, tokens, labels, c: Dict, mm):
    eps, hp = c["norm_epsilon"], c["headdim"]
    emb = params["embed"]["embedding"]
    x = emb[tokens]

    @jax.checkpoint
    def layer(x, p):
        q = p["ssm"]
        hn = rmsnorm(x, p["ssm_norm"]["scale"], eps)
        z = mm("bsd,de->bse", hn, q["wz"]["kernel"])
        xs = silu(causal_conv(mm("bsd,de->bse", hn, q["wx"]["kernel"]),
                              q["conv_x"]))
        bm = silu(causal_conv(mm("bsd,dn->bsn", hn, q["wB"]["kernel"]),
                              q["conv_B"]))
        cm = silu(causal_conv(mm("bsd,dn->bsn", hn, q["wC"]["kernel"]),
                              q["conv_C"]))
        dt = jax.nn.softplus(mm("bsd,dh->bsh", hn, q["wdt"]["kernel"])
                             + q["dt_bias"])
        b, s, di = xs.shape
        xh = xs.reshape(b, s, di // hp, hp)
        y = recurrence(xh, dt, -jnp.exp(q["A_log"]), bm, cm)
        y = (y + q["D"][:, None] * xh).reshape(b, s, di) * silu(z)
        y = rmsnorm(y, q["norm"]["scale"], eps)
        return x + mm("bse,ed->bsd", y, q["wo"]["kernel"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    return lm_loss(x, emb, labels, mm, z_loss=Z_LOSS)
