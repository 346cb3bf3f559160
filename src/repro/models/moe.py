"""Mixture-of-Experts layer: top-k routing, capacity-bounded sorted dispatch,
expert parallelism over the "model" mesh axis.

Dispatch is *per batch row* (buffers [B, E, C, d]): each (data, model) device
multiplies its local tokens against its local experts, so no all-to-all is
required — the only collectives are the contraction psums XLA already inserts
for tensor parallelism.  Router statistics (tokens/expert, dropped tokens) are
returned as dynamic Nugget-signature entries (DESIGN.md §2).

:func:`moe_held` is the serving layer of the ``mla_moe`` and ``kda_mla_moe``
families: it holds ``n_held`` of the router's experts (one chip's share
under expert parallelism), routes over all of them, and computes its own
experts' part of the result for every token routed to them, with no token
dropped.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.layers import ParamSpec


def moe_specs(cfg: ArchConfig) -> Dict[str, Any]:
    """The router keeps all ``n_experts`` outputs; expert weights are those
    of the ``n_local`` experts held here."""
    m = cfg.moe
    d, fe, ne = cfg.d_model, m.d_expert, m.n_local
    specs: Dict[str, Any] = {
        "router": {"kernel": ParamSpec((d, m.n_experts), ("embed", "experts"),
                                       "scaled")},
        "wi": ParamSpec((ne, d, fe), ("experts", "embed", "expert_mlp"),
                        "scaled"),
        "wo": ParamSpec((ne, fe, d), ("experts", "expert_mlp", "embed"),
                        "scaled"),
    }
    if cfg.held_experts:
        # e_score_correction_bias: moves selection only, not the gates
        specs["router"]["score_bias"] = ParamSpec((m.n_experts,), (None,),
                                                  "normal")
    if cfg.glu:
        specs["wg"] = ParamSpec((ne, d, fe),
                                ("experts", "embed", "expert_mlp"), "scaled")
    if m.n_shared_experts:
        specs["shared"] = L.mlp_specs(d, m.d_shared or cfg.d_ff, glu=cfg.glu)
    return specs


def capacity(seq_len: int, m: MoEConfig) -> int:
    c = int(math.ceil(seq_len * m.top_k / m.n_experts * m.capacity_factor))
    return max(8, -(-c // 8) * 8)          # pad to 8 for TPU-friendly tiling


def route(router_params, x: jax.Array, m: MoEConfig, rng=None):
    """x: [B,S,d] -> (expert ids [B,S,k], gates [B,S,k], aux dict)."""
    logits = L.dense(router_params, x, jnp.float32)        # [B,S,E]
    if rng is not None and m.router_jitter > 0:
        logits = logits + m.router_jitter * jax.random.normal(rng, logits.shape)
    gates_full = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates_full, m.top_k)      # [B,S,k]
    top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style)
    T = x.shape[0] * x.shape[1]
    me = jnp.mean(gates_full.reshape(-1, m.n_experts), axis=0)
    onehot = jax.nn.one_hot(top_e[..., 0], m.n_experts, dtype=jnp.float32)
    ce = jnp.mean(onehot.reshape(-1, m.n_experts), axis=0)
    aux_loss = m.n_experts * jnp.sum(me * ce) * m.aux_loss_coef
    return top_e, top_g, {"router_aux_loss": aux_loss, "router_logits_max":
                          jnp.max(jnp.abs(logits))}


def dispatch_indices(top_e: jax.Array, k: int, n_experts: int, cap: int):
    """Per batch row, sorted capacity-bounded slotting.

    top_e: [S, k] expert ids for one row -> (slot [S*k] int32 in [0, E*cap),
    keep [S*k] bool).  Tokens beyond an expert's capacity are dropped
    (standard capacity-factor semantics).
    """
    flat_e = top_e.reshape(-1)                              # [S*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - start[sorted_e].astype(jnp.int32)
    keep_sorted = pos < cap
    slot_sorted = sorted_e * cap + jnp.minimum(pos, cap - 1)
    # unsort back to (token, k) order
    inv = jnp.argsort(order)
    return slot_sorted[inv].astype(jnp.int32), keep_sorted[inv]


def moe_mlp(params, cfg: ArchConfig, x: jax.Array, *, rng=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    m = cfg.moe
    b, s, d = x.shape
    cap = capacity(s, m)
    dtype = x.dtype

    top_e, top_g, aux = route(params["router"], x, m, rng)

    slot, keep = jax.vmap(lambda e: dispatch_indices(e, m.top_k, m.n_experts, cap))(top_e)
    # scatter tokens into expert buffers [B, E*cap, d]
    tok = jnp.repeat(x, m.top_k, axis=1)                    # [B, S*k, d]
    buf = jnp.zeros((b, m.n_experts * cap, d), dtype)
    wmask = keep[..., None].astype(dtype)
    buf = jax.vmap(lambda bf, sl, tk, km: bf.at[sl].add(tk * km))(
        buf, slot, tok, wmask)
    buf = buf.reshape(b, m.n_experts, cap, d)
    buf = shard(buf, "batch", "experts", None, None)

    # expert MLPs (grouped matmul; E sharded over "model", B over data)
    wi, wo = params["wi"].astype(dtype), params["wo"].astype(dtype)
    h = jnp.einsum("becd,edf->becf", buf, wi)
    h = L.ACTS[cfg.act](h)
    if "wg" in params:
        h = h * jnp.einsum("becd,edf->becf", buf, params["wg"].astype(dtype))
    out_buf = jnp.einsum("becf,efd->becd", h, wo)
    out_buf = shard(out_buf, "batch", "experts", None, None)
    out_buf = out_buf.reshape(b, m.n_experts * cap, d)

    # gather back + combine with gates
    gathered = jax.vmap(lambda ob, sl: ob[sl])(out_buf, slot)   # [B,S*k,d]
    gathered = gathered * (keep[..., None].astype(dtype) *
                           top_g.reshape(b, -1)[..., None].astype(dtype))
    y = jnp.sum(gathered.reshape(b, s, m.top_k, d), axis=2)

    if m.n_shared_experts:
        y = y + L.mlp(params["shared"], x, cfg.act, dtype)

    # ---- dynamic Nugget-signature entries -------------------------------
    onehot_counts = jnp.zeros((m.n_experts,), jnp.int32).at[top_e.reshape(-1)].add(1)
    aux["expert_tokens"] = onehot_counts                     # [E]
    aux["dropped_tokens"] = jnp.sum(~keep)
    return shard(y, "batch", "seq", "act_embed"), aux


# ---------------------------------------------------------------------------
# Held experts, drop-free (serving, family mla_moe)
# ---------------------------------------------------------------------------

def route_sigmoid(router_params, x: jax.Array, m: MoEConfig):
    """DeepSeek-V3 routing (``noaux_tc`` with one group): scores
    sigmoid(x·W) in float32; the top k of the scores plus the correction
    bias are chosen; their gates are the chosen scores without the bias,
    normalised to sum 1 and scaled by ``routed_scale``.
    x: [T,d] -> (expert ids [T,k], gates [T,k] f32)."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router_params["kernel"].astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(
        scores + router_params["score_bias"].astype(f32), m.top_k)
    top_g = jnp.take_along_axis(scores, top_e, axis=-1)
    top_g = top_g / (jnp.sum(top_g, -1, keepdims=True) + 1e-20)
    return top_e, top_g * m.routed_scale


def _held_experts(params, act: str, x, local, gates, n: int):
    """Every held expert runs over every token, and a token's output from
    it is weighted by its gate where the router chose it, by 0 elsewhere:
    no token can be dropped, and the cost does not follow the routing.
    x: [T,d]; local: [T,k] held-expert index, outside ``0..n-1`` for an
    expert not held; gates: [T,k].  Returns y [T,d] in float32."""
    dtype = x.dtype
    # [T,n]: a token chooses an expert once at most
    w = jnp.sum(jnp.where(local[..., None] == jnp.arange(n),
                          gates[..., None], 0.0), axis=1)
    h = jnp.einsum("td,edf->etf", x, params["wi"].astype(dtype))
    h = L.ACTS[act](h)
    if "wg" in params:
        h = h * jnp.einsum("td,edf->etf", x, params["wg"].astype(dtype))
    out = jnp.einsum("etf,efd->etd", h, params["wo"].astype(dtype))
    return jnp.sum(out.astype(jnp.float32) * w.T[:, :, None], axis=0)


def moe_held(params, cfg: ArchConfig, x: jax.Array
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expert layer as one chip of an expert-parallel group runs it:
    the router scores all ``n_experts``; the chip computes the part of the
    result its ``n_held`` experts give (gates normalised over all k chosen)
    plus the shared experts.  Each held expert takes the whole batch, so no
    token is dropped, whatever the routing.  On one chip the layer runs
    without its exchange.

    aux: ``expert_tokens`` [n_experts] (the router's choices, held or
    not), ``held_tokens`` (pairs routed to the held experts),
    ``dropped_tokens``."""
    m = cfg.moe
    b, s, d = x.shape
    t, n = b * s, m.n_local
    xt = x.reshape(t, d)
    top_e, top_g = route_sigmoid(params["router"], xt, m)
    counts = jnp.zeros((m.n_experts,), jnp.int32).at[
        top_e.reshape(-1)].add(1)
    load = jax.lax.dynamic_slice_in_dim(counts, m.held_first, n)
    y = _held_experts(params, cfg.act, xt, top_e - m.held_first, top_g, n)
    y = y.astype(x.dtype).reshape(b, s, d)
    if m.n_shared_experts:
        y = y + L.mlp(params["shared"], x, cfg.act, x.dtype)
    aux = {"expert_tokens": counts, "held_tokens": jnp.sum(load),
           # each held expert takes every token (see _held_experts)
           "dropped_tokens": jnp.zeros((), jnp.int32)}
    return shard(y, "batch", "seq", "act_embed"), aux
