"""Plain float32 reference of Kimi Linear's hybrid decoder
(Kimi-Linear-48B-A3B), written from the paper (Kimi Linear, arXiv:2510.26692
§3) and the published configuration, for one chip's share of an
expert-parallel deployment.

- Pre-norm blocks with RMSNorm (``rms_norm_eps``).  Each layer's attention is
  Kimi Delta Attention (the layers of ``linear_attn_config.kda_layers``) or
  multi-head latent attention (its ``full_attn_layers``); the first
  ``first_k_dense_replace`` layers end in a SwiGLU MLP of width
  ``intermediate_size``, the rest in the expert layer.
- KDA, per head of width D (``linear_attn_config.head_dim``), token by token:
  q, k, v = SiLU(causal depthwise conv(W x)) of width
  ``short_conv_kernel_size``, each with its own convolution; q and k
  L2-normalised (eps 1e-6 under the root), q scaled by D^-1/2;
  b_t = sigmoid(W_b x_t) one scalar per head; the per-channel decay
  log a_t = -exp(A_log) softplus(W_f_up W_f_down x_t + dt_bias); the state
  S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T from S_0 = 0,
  o_t = S_t^T q_t; the output W_o[sigmoid(W_g_up W_g_down x_t + bias) *
  RMSNorm_head(o_t)].  The recurrence runs in float32 with its products at
  ``HIGHEST``; only the projections go through ``mm``, so the fp8 control
  rounds the weights' products, not the state.
- Latent attention as ``deepseek_v3`` computes it (no query latent; the
  latent c_kv RMS-normed with eps 1e-6; keys and values decompressed;
  softmax scale 1/sqrt(nope + rope), ``deepseek_v3.ATTN_ROWS`` query rows at
  a time), with no rotation (``mla_use_nope``): the ``qk_rope_head_dim``
  columns of q and of the one shared key enter the scores as projected.
- The expert layer is ``deepseek_v3``'s: sigmoid scores over all
  ``published.num_experts`` experts, the top ``num_experts_per_token`` of
  the scores plus the correction bias chosen (one group), gates the chosen
  scores normalised to sum 1 (``moe_renormalize``) times
  ``routed_scaling_factor``; this chip holds ``num_experts`` of them, those
  of ``expert_parallel.rank``, each applied to every token and weighted by
  its gate where chosen; ``num_shared_experts`` shared experts of the
  expert width added to every token.
- A final RMSNorm and an untied LM head.

Departures from arXiv:2510.26692 and the released model: the weights are
drawn (see the configuration's ``assumed``); only ``num_experts`` of the
256 experts are held, and the absent experts' part is left out, as the
program leaves it out; there is no pipeline exchange.

Leaf names and shapes follow the program's layout so that one seed gives one
set of weights: stack ``layerNN_<kind>_<ffn>`` holds layer NN and, where the
schedule's period repeats, the layers a period apart (:func:`layers`); the
KDA leaves are ``wq``/``wk``/``wv``, ``conv_q``/``conv_k``/``conv_v``
([width, channels]), ``wb``, ``wf_a``/``wf_b`` (decay), ``wg_a``/``wg_b``
(output gate), ``A_log``, ``dt_bias``, ``o_norm``, ``wo``.  The rows of a
batch run one at a time, so that the attention's transients of one request
fit beside the weights.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.reference import deepseek_v3 as ds
from bench.reference.train import HIGHEST, Leaf, lm_loss, rmsnorm, silu

Z_LOSS = 1e-4
L2_EPS = 1e-6


def _kinds(c: Dict) -> List[str]:
    full = set(c["linear_attn_config"]["full_attn_layers"])
    return ["mla" if i + 1 in full else "kda"
            for i in range(c["num_hidden_layers"])]


def layers(c: Dict) -> List[Tuple[str, int]]:
    """Per layer, the stack that holds its weights and its row there.
    Where the stacks of the schedule's period (the distance between its
    first two latent layers) repeat, each position of the period has a
    stack whose rows are that position's layers, a period apart; every
    other stretch of layers of one stack is one stack."""
    kinds = _kinds(c)
    stacks = [f"{k}_{'mlp' if i < c['first_k_dense_replace'] else 'moe'}"
              for i, k in enumerate(kinds)]
    full = c["linear_attn_config"]["full_attn_layers"]
    period = full[1] - full[0]
    out: List[Tuple[str, int]] = []
    i, n = 0, len(stacks)
    while i < n:
        w = stacks[i:i + period]
        reps = 0
        while (i + (reps + 1) * period <= n
               and stacks[i + reps * period:i + (reps + 1) * period] == w):
            reps += 1
        if reps > 1 and len(set(w)) > 1:
            out += [(f"layer{i + q:02d}_{w[q]}", r)
                    for r in range(reps) for q in range(period)]
            i += reps * period
            continue
        r = 1
        while i + r < n and stacks[i + r] == stacks[i]:
            r += 1
        out += [(f"layer{i:02d}_{stacks[i]}", k) for k in range(r)]
        i += r
    return out


def _moe_conf(c: Dict) -> Dict:
    """The configuration keys ``deepseek_v3.experts`` reads."""
    return {"n_routed_experts": c["num_experts"],
            "published": {"n_routed_experts": c["published"]["num_experts"]},
            "expert_parallel": c["expert_parallel"],
            "num_experts_per_tok": c["num_experts_per_token"],
            "routed_scaling_factor": c["routed_scaling_factor"]}


def param_specs(c: Dict):
    d, v = c["hidden_size"], c["vocab_size"]
    h, nope, rope, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
    r = c["kv_lora_rank"]
    lin = c["linear_attn_config"]
    hk, rank, kw = (lin["num_heads"] * lin["head_dim"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    fe, e = c["moe_intermediate_size"], c["num_experts"]
    router = c["published"]["num_experts"]

    def kda(L):
        return {"A_log": Leaf((L, lin["num_heads"]), "a_log"),
                "conv_k": Leaf((L, kw, hk), "scaled"),
                "conv_q": Leaf((L, kw, hk), "scaled"),
                "conv_v": Leaf((L, kw, hk), "scaled"),
                "dt_bias": Leaf((L, hk), "zeros"),
                "o_norm": {"scale": Leaf((L, lin["head_dim"]), "ones")},
                "wb": {"kernel": Leaf((L, d, lin["num_heads"]), "normal")},
                "wf_a": {"kernel": Leaf((L, d, rank), "normal")},
                "wf_b": {"kernel": Leaf((L, rank, hk), "normal")},
                "wg_a": {"kernel": Leaf((L, d, rank), "normal")},
                "wg_b": {"bias": Leaf((L, hk), "zeros"),
                         "kernel": Leaf((L, rank, hk), "normal")},
                "wk": {"kernel": Leaf((L, d, hk), "scaled")},
                "wo": {"kernel": Leaf((L, hk, d), "scaled")},
                "wq": {"kernel": Leaf((L, d, hk), "scaled")},
                "wv": {"kernel": Leaf((L, d, hk), "scaled")}}

    def mla(L):
        return {"kv_norm": {"scale": Leaf((L, r), "ones")},
                "wkv_a": {"kernel": Leaf((L, d, r + rope), "scaled")},
                "wkv_b": {"kernel": Leaf((L, r, h, nope + vd), "scaled")},
                "wo": {"kernel": Leaf((L, h, vd, d), "scaled")},
                "wq": {"kernel": Leaf((L, d, h, nope + rope), "normal")}}

    def mlp(L, f):
        return {"wg": {"kernel": Leaf((L, d, f), "scaled")},
                "wi": {"kernel": Leaf((L, d, f), "scaled")},
                "wo": {"kernel": Leaf((L, f, d), "scaled")}}

    def moe(L):
        return {"router": {"kernel": Leaf((L, d, router), "scaled"),
                           "score_bias": Leaf((L, router), "normal")},
                "shared": mlp(L, c["num_shared_experts"] * fe),
                "wg": Leaf((L, e, d, fe), "scaled"),
                "wi": Leaf((L, e, d, fe), "scaled"),
                "wo": Leaf((L, e, fe, d), "scaled")}

    rows: Dict[str, int] = {}
    for stack, row in layers(c):
        rows[stack] = max(rows.get(stack, 0), row + 1)
    specs = {"embed": {"embedding": Leaf((v, d), "normal")},
             "final_norm": {"scale": Leaf((d,), "ones")},
             "lm_head": {"kernel": Leaf((d, v), "scaled")}}
    for stack, L in rows.items():
        _, kind, ffn = stack.split("_")
        specs[stack] = {"attn_norm": {"scale": Leaf((L, d), "ones")},
                        "mlp_norm": {"scale": Leaf((L, d), "ones")},
                        ("kda" if kind == "kda" else "attn"):
                            kda(L) if kind == "kda" else mla(L),
                        ffn: (mlp(L, c["intermediate_size"]) if ffn == "mlp"
                              else moe(L))}
    return specs


def causal_conv(x, w):
    """x: [b, s, ch]; w: [width, ch]; y_t = sum_i w_i x_{t-width+1+i}."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(k))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_attention(p, x, c: Dict, mm):
    """KDA over x: [s, d] (normed, one request) -> [s, d]."""
    lin = c["linear_attn_config"]
    nh, dk = lin["num_heads"], lin["head_dim"]
    s = x.shape[0]

    def branch(w, conv):
        y = silu(causal_conv(mm("sd,de->se", x, w["kernel"])[None], conv)[0])
        return y.reshape(s, nh, dk)

    q = l2norm(branch(p["wq"], p["conv_q"])) / math.sqrt(dk)
    k = l2norm(branch(p["wk"], p["conv_k"]))
    v = branch(p["wv"], p["conv_v"])
    f = mm("sr,re->se", mm("sd,dr->sr", x, p["wf_a"]["kernel"]),
           p["wf_b"]["kernel"]) + p["dt_bias"]
    g = (-jnp.exp(p["A_log"])[:, None]
         * jax.nn.softplus(f.reshape(s, nh, dk)))               # log a
    beta = jax.nn.sigmoid(mm("sd,dh->sh", x, p["wb"]["kernel"]))

    def step(state, xs):                             # state: [h, dk, dv]
        qt, kt, vt, gt, bt = xs
        decayed = jnp.exp(gt)[..., None] * state     # Diag(a_t) S_{t-1}
        # (I - b k k^T) Diag(a) S + b k v^T, the projection applied as the
        # rank-one update it is
        k_s = jnp.einsum("hk,hkv->hv", kt, decayed, precision=HIGHEST)
        state = decayed + bt[:, None, None] * kt[:, :, None] * (
            vt - k_s)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((nh, dk, dk)), (q, k, v, g, beta))
    gate = mm("sr,re->se", mm("sd,dr->sr", x, p["wg_a"]["kernel"]),
              p["wg_b"]["kernel"]) + p["wg_b"]["bias"]
    o = rmsnorm(o, p["o_norm"]["scale"], c["rms_norm_eps"])
    o = o.reshape(s, nh * dk) * jax.nn.sigmoid(gate)
    return mm("se,ed->sd", o, p["wo"]["kernel"])


def latent_attention(a, x, c: Dict, mm):
    """MLA with no rotation over x: [s, d] (normed, one request)."""
    nope, rope_d, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                       c["kv_lora_rank"])
    x = x[None]
    q = mm("bsd,dhk->bshk", x, a["wq"]["kernel"])
    kv = mm("bsd,dk->bsk", x, a["wkv_a"]["kernel"])
    c_kv = rmsnorm(kv[..., :r], a["kv_norm"]["scale"], ds.KV_NORM_EPS)
    k_pe = kv[..., None, r:]                                    # 1 head
    kvb = mm("bsc,chk->bshk", c_kv, a["wkv_b"]["kernel"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:-1] + (rope_d,))], -1)
    out = ds.causal_attention(q, k, v, mm)
    return mm("bshk,hkd->bsd", out, a["wo"]["kernel"])[0]


def _hidden_row(params, tokens, c: Dict, mm):
    """The final-normed hidden states [s, d] of one request's tokens."""
    eps = c["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    moe_c = _moe_conf(c)
    for stack, row in layers(c):
        p = jax.tree.map(lambda a: a[row], params[stack])
        h = rmsnorm(x, p["attn_norm"]["scale"], eps)
        x = x + (delta_attention(p["kda"], h, c, mm) if "kda" in p
                 else latent_attention(p["attn"], h, c, mm))
        h = rmsnorm(x, p["mlp_norm"]["scale"], eps)[None]
        x = x + (ds.swiglu(p["mlp"], h, mm) if "mlp" in p
                 else ds.experts(p["moe"], h, moe_c, mm))[0]
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def hidden(params, tokens, c: Dict, mm):
    """The final-normed hidden states [b, s, d] of ``tokens``, a row at a
    time."""
    return jax.lax.map(
        jax.checkpoint(lambda t: _hidden_row(params, t, c, mm)), tokens)


def loss(params, tokens, labels, c: Dict, mm):
    x = hidden(params, tokens, c, mm)
    return lm_loss(x, params["lm_head"]["kernel"].T, labels, mm,
                   z_loss=Z_LOSS)


def logits(params, tokens, c: Dict, mm, start: int):
    """Logits [b, s - start, vocab] of positions ``start ..`` of ``tokens``."""
    x = hidden(params, tokens, c, mm)[:, start:]
    return mm("bsd,dv->bsv", x, params["lm_head"]["kernel"])
