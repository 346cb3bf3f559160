"""Plain float32 reference of a DeepSeek-V3 decoder (Moonlight-16B-A3B),
written from the published architecture (DeepSeek-V3 Technical Report,
arXiv:2412.19437 §2.1; HF ``DeepseekV3`` modelling) for one chip's share
of an expert-parallel deployment.

- Pre-norm blocks with RMSNorm (``rms_norm_eps``).  The first
  ``first_k_dense_replace`` layers end in a SwiGLU MLP
  ``down(silu(gate(x)) * up(x))`` of width ``intermediate_size``; the rest
  in the expert layer.
- Multi-head latent attention with no query latent (``q_lora_rank`` null):
  q is a projection to per-head (``qk_nope_head_dim`` | ``qk_rope_head_dim``)
  parts; ``kv_a_proj_with_mqa`` gives the latent c_kv (``kv_lora_rank``
  wide, RMS-normed with eps 1e-6) and one rope key shared by all heads;
  ``kv_b_proj`` expands c_kv to per-head k_nope and v.  RoPE (base
  ``rope_theta``) as DeepSeek's code applies it: the interleaved pairs of
  the rope part are moved to halves, then rotated by halves.  Causal
  softmax with scale 1/sqrt(nope + rope), attention computed decompressed
  and ``ATTN_ROWS`` query rows at a time.
- The expert layer: scores sigmoid(x·W_r) over all
  ``published.n_routed_experts`` experts; the top ``num_experts_per_tok`` of
  the scores plus ``e_score_correction_bias`` are chosen (one group, so no
  group limit); their gates are the chosen scores, normalised to sum 1 and
  multiplied by ``routed_scaling_factor``.  This chip holds
  ``n_routed_experts`` of them, those of ``expert_parallel.rank``: each
  held expert's SwiGLU (width ``moe_intermediate_size``) is applied to every
  token and weighted by its gate where chosen, 0 elsewhere; the absent
  experts' part is left out, as the program leaves it out.  The shared
  experts are one SwiGLU of width ``n_shared_experts`` times the expert
  width, added to every token.
- A final RMSNorm and an untied LM head.

Leaf names and shapes follow the program's layout (``dense_layers`` and
``layers`` stacked over depth; ``wi`` the gate and ``wg`` the up
projection; the correction bias ``moe/router/score_bias``) so that one seed
gives one set of weights.  The loss adds 1e-4·mean(lse²) (z-loss) to the
cross-entropy, as the program's training objective does.  ``logits`` gives
the head's output for serving comparisons.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.train import Leaf, lm_loss, rmsnorm, silu

ATTN_ROWS = 512
Z_LOSS = 1e-4
KV_NORM_EPS = 1e-6


def _held(c: Dict):
    """(first expert held here, experts held, experts the router scores)."""
    e = c["n_routed_experts"]
    return (c["expert_parallel"]["rank"] * e, e,
            c["published"]["n_routed_experts"])


def param_specs(c: Dict):
    n, k = c["num_hidden_layers"], c["first_k_dense_replace"]
    m = n - k
    d, v, h = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    fe = c["moe_intermediate_size"]
    _, e, router = _held(c)

    def attn(L):
        return {"kv_norm": {"scale": Leaf((L, r), "ones")},
                "wkv_a": {"kernel": Leaf((L, d, r + rope), "scaled")},
                "wkv_b": {"kernel": Leaf((L, r, h, nope + vd), "scaled")},
                "wo": {"kernel": Leaf((L, h, vd, d), "scaled")},
                "wq": {"kernel": Leaf((L, d, h, nope + rope), "normal")}}

    def mlp(L, f):
        return {"wg": {"kernel": Leaf((L, d, f), "scaled")},
                "wi": {"kernel": Leaf((L, d, f), "scaled")},
                "wo": {"kernel": Leaf((L, f, d), "scaled")}}

    def norms(L):
        return {"attn_norm": {"scale": Leaf((L, d), "ones")},
                "mlp_norm": {"scale": Leaf((L, d), "ones")}}

    return {
        "dense_layers": {"attn": attn(k),
                         "mlp": mlp(k, c["intermediate_size"]), **norms(k)},
        "embed": {"embedding": Leaf((v, d), "normal")},
        "final_norm": {"scale": Leaf((d,), "ones")},
        "layers": {
            "attn": attn(m), **norms(m),
            "moe": {
                "router": {"kernel": Leaf((m, d, router), "scaled"),
                           "score_bias": Leaf((m, router), "normal")},
                "shared": mlp(m, c["n_shared_experts"] * fe),
                "wg": Leaf((m, e, d, fe), "scaled"),
                "wi": Leaf((m, e, d, fe), "scaled"),
                "wo": Leaf((m, e, fe, d), "scaled"),
            },
        },
        "lm_head": {"kernel": Leaf((d, v), "scaled")},
    }


def rope(x, theta):
    """x: [b, s, heads, r] with DeepSeek's interleaved pairs; returns the
    rotated vector with the pairs moved to (first half, second half)."""
    b, s, nh, r = x.shape
    x = x.reshape(b, s, nh, r // 2, 2).swapaxes(-1, -2).reshape(b, s, nh, r)
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv          # [s, r/2]
    ang = jnp.concatenate([ang, ang], -1)[:, None]                  # [s,1,r]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def causal_attention(q, k, v, mm):
    """q, k: [b, s, h, dk]; v: [b, s, h, dv] -> [b, s, h, dv]."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    rows = min(ATTN_ROWS, s)
    nb = -(-s // rows)
    q = jnp.pad(q, ((0, 0), (0, nb * rows - s), (0, 0), (0, 0)))
    qb = q.reshape(b, nb, rows, h, dk)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(qi, i):
        scores = mm("brhk,bshk->bhrs", qi, k) / math.sqrt(dk)
        qpos = i * rows + jnp.arange(rows)
        scores = jnp.where(qpos[:, None] >= kpos[None, :], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return mm("bhrs,bshk->brhk", p, v)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.moveaxis(qb, 1, 0), jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nb * rows, h, dv)[:, :s]


def latent_attention(a, x, c: Dict, mm):
    """x: [b, s, d] (normed) -> [b, s, d]."""
    nope, rope_d = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    r, theta = c["kv_lora_rank"], float(c["rope_theta"])
    q = mm("bsd,dhk->bshk", x, a["wq"]["kernel"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = mm("bsd,dk->bsk", x, a["wkv_a"]["kernel"])
    c_kv = rmsnorm(kv[..., :r], a["kv_norm"]["scale"], KV_NORM_EPS)
    k_pe = rope(kv[..., None, r:], theta)                          # 1 head
    kvb = mm("bsc,chk->bshk", c_kv, a["wkv_b"]["kernel"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q = jnp.concatenate([q_nope, rope(q_pe, theta)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:-1] + (rope_d,))], -1)
    return mm("bshk,hkd->bsd", causal_attention(q, k, v, mm),
              a["wo"]["kernel"])


def swiglu(p, x, mm):
    gate = mm("bsd,df->bsf", x, p["wi"]["kernel"])
    up = mm("bsd,df->bsf", x, p["wg"]["kernel"])
    return mm("bsf,fd->bsd", silu(gate) * up, p["wo"]["kernel"])


def experts(p, x, c: Dict, mm):
    """The expert layer's output on this chip: its held experts' part and
    the shared experts.  x: [b, s, d] (normed)."""
    first, held, router = _held(c)
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm("bsd,de->bse", x, p["router"]["kernel"]))
    _, idx = jax.lax.top_k(scores + p["router"]["score_bias"], k)
    g = jnp.take_along_axis(scores, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * c["routed_scaling_factor"]
    gates = jnp.sum(jax.nn.one_hot(idx, router) * g[..., None], -2)
    gates = gates[..., first:first + held]                     # [b, s, held]

    def one(y, xs):
        wi, wg, wo, gate = xs
        h = silu(mm("bsd,df->bsf", x, wi)) * mm("bsd,df->bsf", x, wg)
        return y + gate[..., None] * mm("bsf,fd->bsd", h, wo), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (p["wi"], p["wg"], p["wo"],
                                                 jnp.moveaxis(gates, -1, 0)))
    return y + swiglu(p["shared"], x, mm)


def hidden(params, tokens, c: Dict, mm):
    """The final-normed hidden states [b, s, d] of ``tokens``."""
    eps = c["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]

    def block(ffn):
        @jax.checkpoint
        def layer(x, p):
            x = x + latent_attention(
                p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps), c, mm)
            return x + ffn(p, rmsnorm(x, p["mlp_norm"]["scale"], eps)), None
        return layer

    x, _ = jax.lax.scan(block(lambda p, h: swiglu(p["mlp"], h, mm)), x,
                        params["dense_layers"])
    x, _ = jax.lax.scan(block(lambda p, h: experts(p["moe"], h, c, mm)), x,
                        params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def loss(params, tokens, labels, c: Dict, mm):
    x = hidden(params, tokens, c, mm)
    return lm_loss(x, params["lm_head"]["kernel"].T, labels, mm,
                   z_loss=Z_LOSS)


def logits(params, tokens, c: Dict, mm, start: int):
    """Logits [b, s - start, vocab] of positions ``start ..`` of ``tokens``."""
    x = hidden(params, tokens, c, mm)[:, start:]
    return mm("bsd,dv->bsv", x, params["lm_head"]["kernel"])
