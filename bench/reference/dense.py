"""Plain float32 reference of a dense decoder of the Qwen3 family, written
from the published architecture (Qwen3 Technical Report; HF ``Qwen3``
modelling): pre-norm blocks with RMSNorm; grouped-query attention with
RMSNorm on each query and key head, rotary embedding on the two halves of
each head (base ``rope_theta``) and a causal softmax; a SwiGLU MLP
``down(silu(gate(x)) * up(x))``; a final RMSNorm and the LM head tied to the
embedding.  Leaf names follow the program's layout so that one seed gives
one set of weights: ``wi`` is the gate projection and ``wg`` the up
projection.  The loss adds 1e-4·mean(lse²) (z-loss) to the cross-entropy,
as the program's training objective does.  ``logits`` gives the head's
output for serving comparisons.

Attention runs ``ATTN_ROWS`` query rows at a time and the head
``train.lm_loss`` 512 tokens at a time, each recomputed in the backward
pass, so that a float32 step at the cell's widths fits one chip.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.train import Leaf, lm_loss, rmsnorm, silu

ATTN_ROWS = 512
Z_LOSS = 1e-4


def param_specs(c: Dict):
    n, d, f, v = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    return {
        "embed": {"embedding": Leaf((v, d), "normal")},
        "final_norm": {"scale": Leaf((d,), "ones")},
        "layers": {
            "attn": {
                "k_norm": {"scale": Leaf((n, hd), "ones")},
                "q_norm": {"scale": Leaf((n, hd), "ones")},
                "wk": {"kernel": Leaf((n, d, kv, hd), "scaled")},
                "wo": {"kernel": Leaf((n, h, hd, d), "scaled")},
                "wq": {"kernel": Leaf((n, d, h, hd), "normal")},
                "wv": {"kernel": Leaf((n, d, kv, hd), "scaled")},
            },
            "attn_norm": {"scale": Leaf((n, d), "ones")},
            "mlp": {"wg": {"kernel": Leaf((n, d, f), "scaled")},
                    "wi": {"kernel": Leaf((n, d, f), "scaled")},
                    "wo": {"kernel": Leaf((n, f, d), "scaled")}},
            "mlp_norm": {"scale": Leaf((n, d), "ones")},
        },
    }


def rope(x, theta):
    """x: [b, s, heads, hd]; rotates (first half, second half) pairs."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(hd // 2, dtype=jnp.float32)
                           / (hd // 2)))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # [s, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, mm):
    """q: [b, s, h, hd]; k, v: [b, s, kv, hd] -> [b, s, h, hd]."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rows = min(ATTN_ROWS, s)
    nb = -(-s // rows)
    q = jnp.pad(q, ((0, 0), (0, nb * rows - s), (0, 0), (0, 0)))
    qb = q.reshape(b, nb, rows, kv, h // kv, hd)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(qi, i):
        scores = mm("brngk,bsnk->bngrs", qi, k) / math.sqrt(hd)
        qpos = i * rows + jnp.arange(rows)
        scores = jnp.where(qpos[:, None] >= kpos[None, :], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return mm("bngrs,bsnk->brngk", p, v)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.moveaxis(qb, 1, 0), jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nb * rows, h, hd)[:, :s]


def hidden(params, tokens, c: Dict, mm):
    """The final-normed hidden states [b, s, d] of ``tokens``."""
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    x = params["embed"]["embedding"][tokens]

    @jax.checkpoint
    def layer(x, p):
        a = p["attn"]
        hn = rmsnorm(x, p["attn_norm"]["scale"], eps)
        q = mm("bsd,dhk->bshk", hn, a["wq"]["kernel"])
        k = mm("bsd,dhk->bshk", hn, a["wk"]["kernel"])
        v = mm("bsd,dhk->bshk", hn, a["wv"]["kernel"])
        q = rope(rmsnorm(q, a["q_norm"]["scale"], eps), theta)
        k = rope(rmsnorm(k, a["k_norm"]["scale"], eps), theta)
        x = x + mm("bshk,hkd->bsd", causal_attention(q, k, v, mm),
                   a["wo"]["kernel"])
        m = p["mlp"]
        hn = rmsnorm(x, p["mlp_norm"]["scale"], eps)
        gate = mm("bsd,df->bsf", hn, m["wi"]["kernel"])
        up = mm("bsd,df->bsf", hn, m["wg"]["kernel"])
        return x + mm("bsf,fd->bsd", silu(gate) * up, m["wo"]["kernel"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def loss(params, tokens, labels, c: Dict, mm):
    x = hidden(params, tokens, c, mm)
    return lm_loss(x, params["embed"]["embedding"], labels, mm,
                   z_loss=Z_LOSS)


def logits(params, tokens, c: Dict, mm, start: int):
    """Logits [b, s - start, vocab] of positions ``start ..`` of ``tokens``."""
    x = hidden(params, tokens, c, mm)[:, start:]
    return mm("bsd,vd->bsv", x, params["embed"]["embedding"])
