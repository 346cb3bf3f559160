"""Decoder-only LM covering dense / MoE / MLA-MoE / KDA-MLA-MoE / SSM /
hybrid / VLM families.

Layers are *scanned* (params stacked on a leading "layer" axis) so the HLO
stays compact for 88-layer archs and remat applies per-layer.  Per-layer
static attention windows (gemma3 5:1 local:global) ride along as scan xs.
Hybrid (zamba2) uses grouped scans with one SHARED attention block between
groups (its params live outside the scan and are reused — paper-faithful to
the released family).  The ``kda_mla_moe`` family (Kimi Linear) mixes two
kinds of attention layer; its layers run in the order of their schedule,
one scan per repeated period of the schedule or run of like layers
(:func:`layer_plan`), each parameter stack read by one scan.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, dtype_of
from repro.distributed.sharding import shard
from repro.models import attention as A
from repro.models import kda as K
from repro.models import kvcache as KC
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.attention import HeadLayout
from repro.models.layers import ParamSpec


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """Mesh-dependent derived dimensions (head/vocab padding)."""
    tp: int
    layout: Optional[HeadLayout]
    vocab_pad: int

    @staticmethod
    def make(cfg: ArchConfig, tp: int) -> "ModelDims":
        layout = HeadLayout.make(cfg.attn, tp) if cfg.attn else None
        vpad = tp * math.ceil(cfg.vocab_size / tp)
        return ModelDims(tp, layout, vpad)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def mla_layer_specs(cfg: ArchConfig, *, moe: bool) -> Dict[str, Any]:
    """A layer of the ``mla_moe`` family: latent attention, then the expert
    layer (``moe``) or, in the leading dense layers, a SwiGLU of ``d_ff``."""
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "attn_norm": L.rmsnorm_specs(d),
        "attn": A.mla_specs(cfg.attn, d),
        "mlp_norm": L.rmsnorm_specs(d),
    }
    if moe:
        specs["moe"] = M.moe_specs(cfg)
    else:
        specs["mlp"] = L.mlp_specs(d, cfg.d_ff, glu=cfg.glu)
    return specs


def kda_layer_specs(cfg: ArchConfig, stack: str) -> Dict[str, Any]:
    """A layer of the ``kda_mla_moe`` family of kind ``stack``
    (``<kind>_<ffn>``): KDA or latent attention, then the expert layer or a
    SwiGLU."""
    kind, ffn = stack.split("_")
    if kind == "mla":
        return mla_layer_specs(cfg, moe=ffn == "moe")
    d = cfg.d_model
    specs: Dict[str, Any] = {"attn_norm": L.rmsnorm_specs(d),
                             "kda": K.kda_specs(cfg),
                             "mlp_norm": L.rmsnorm_specs(d)}
    if ffn == "moe":
        specs["moe"] = M.moe_specs(cfg)
    else:
        specs["mlp"] = L.mlp_specs(d, cfg.d_ff, glu=cfg.glu)
    return specs


# one layer of an iteration of a scan of the plan: the parameter stack it
# reads (its row is the iteration), its kind ("kda" or "mla"), its index
# among the layers of its kind at the first iteration, and how far that
# index moves an iteration
PlanLayer = Tuple[str, str, int, int]


def layer_plan(cfg: ArchConfig) -> List[Tuple[int, List[PlanLayer]]]:
    """The ``kda_mla_moe`` schedule as scans: ``(iterations, layers of one
    iteration)``.  Repeats of the schedule's period (3 KDA + 1 latent layer)
    make one scan whose iteration runs a whole period; the rest are runs of
    consecutive layers of one stack.  Parameter stack ``layerNN_<kind>_<ffn>``
    holds layer NN and the layers at NN plus a multiple of its scan's
    stride, one row an iteration: each stack is read by one scan, one row an
    iteration, so the compiler reads its rows as they lie (a stack that
    several scans read was laid out again and copied whole)."""
    kinds = cfg.layer_kinds()
    stacks = [f"{kind}_{'mlp' if i < cfg.first_k_dense else 'moe'}"
              for i, kind in enumerate(kinds)]
    in_kind = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    mla = cfg.kda.mla_layers
    period = mla[1] - mla[0] if len(mla) > 1 else 0
    plan: List[Tuple[int, List[PlanLayer]]] = []
    i, n = 0, len(stacks)
    while i < n:
        w = stacks[i:i + period]
        reps = 0
        while (period and i + (reps + 1) * period <= n
               and stacks[i + reps * period:i + (reps + 1) * period] == w):
            reps += 1
        size = period if reps > 1 and len(set(w)) > 1 else 1
        if size == 1:
            reps = 1
            while i + reps < n and stacks[i + reps] == stacks[i]:
                reps += 1
        plan.append((reps, [
            (f"layer{i + q:02d}_{stacks[i + q]}", kinds[i + q],
             in_kind[i + q], kinds[i:i + size].count(kinds[i + q]))
            for q in range(size)]))
        i += reps * size
    return plan


def run_plan(cfg: ArchConfig, layer, x, aux, cache: Dict[str, Any],
             *, remat: bool = False):
    """Run the ``kda_mla_moe`` layers in schedule order, one scan per entry
    of :func:`layer_plan`.  ``layer(stack, kind, it, j, x, aux, c)`` runs
    row ``it`` of parameter stack ``stack`` (``j`` among the layers of its
    kind) and returns (x, aux, c); ``c`` holds the arrays of ``cache`` of
    the kinds the scan runs, carried through it.  Returns (x, aux) and
    updates ``cache``."""
    for reps, layers in layer_plan(cfg):
        keys = dict.fromkeys(k for _, kind, *_ in layers
                             for k in KC.KIND_KEYS[kind] if k in cache)

        def body(carry, it, layers=layers):
            xc, aux, c = carry
            for stack, kind, j0, dj in layers:
                xc, aux, c = layer(stack, kind, it, j0 + it * dj, xc,
                                   dict(aux), dict(c))
            return (xc, aux, c), None

        (x, aux, written), _ = jax.lax.scan(
            jax.checkpoint(body) if remat else body,
            (x, aux, {k: cache[k] for k in keys}), jnp.arange(reps))
        cache.update(written)
    return x, aux


def layer_specs(cfg: ArchConfig, dims: ModelDims) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {}
    if cfg.family == "mla_moe":
        return mla_layer_specs(cfg, moe=True)
    if cfg.family in ("dense", "moe", "vlm"):
        specs["attn_norm"] = L.rmsnorm_specs(d)
        specs["attn"] = A.attention_specs(cfg.attn, d, dims.layout)
        specs["mlp_norm"] = L.rmsnorm_specs(d)
        if cfg.family == "moe":
            specs["moe"] = M.moe_specs(cfg)
        else:
            specs["mlp"] = L.mlp_specs(d, cfg.d_ff, glu=cfg.glu)
    elif cfg.family in ("ssm", "hybrid"):
        specs["ssm_norm"] = L.rmsnorm_specs(d)
        specs["ssm"] = S.mamba2_specs(cfg)
    return specs


def shared_attn_specs(cfg: ArchConfig, dims: ModelDims) -> Dict[str, Any]:
    d = cfg.d_model
    return {
        "norm": L.rmsnorm_specs(d),
        "attn": A.attention_specs(cfg.attn, d, dims.layout),
        "mlp_norm": L.rmsnorm_specs(d),
        "mlp": L.mlp_specs(d, cfg.d_ff, glu=cfg.glu),
    }


def lm_specs(cfg: ArchConfig, dims: ModelDims) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "embed": {"embedding": ParamSpec((dims.vocab_pad, cfg.d_model),
                                         ("vocab", "embed"), "normal", 1.0)},
        "final_norm": L.rmsnorm_specs(cfg.d_model),
    }
    if cfg.family == "kda_mla_moe":
        for reps, layers in layer_plan(cfg):
            for stack, *_ in layers:
                specs[stack] = L.stack_specs(
                    kda_layer_specs(cfg, stack.split("_", 1)[1]), reps)
    per_layer = layer_specs(cfg, dims)
    if cfg.family == "mla_moe":
        # leading dense layers, then the expert layers, each a scanned stack
        specs["dense_layers"] = L.stack_specs(
            mla_layer_specs(cfg, moe=False), cfg.first_k_dense)
        specs["layers"] = L.stack_specs(per_layer,
                                        cfg.n_layers - cfg.first_k_dense)
    elif cfg.family != "kda_mla_moe":
        specs["layers"] = L.stack_specs(per_layer, cfg.n_layers)
    if cfg.family == "hybrid":
        specs["shared_attn"] = shared_attn_specs(cfg, dims)
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": ParamSpec(
            (cfg.d_model, dims.vocab_pad), ("embed", "vocab"), "scaled")}
    if cfg.n_patches:
        specs["patch_proj"] = L.dense_specs(cfg.d_model, cfg.d_model,
                                            ("embed", None))
    return specs


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _attn_block(p, cfg: ArchConfig, dims: ModelDims, x, positions, window,
                *, plus_one: bool, aux: Dict):
    # named_scope labels survive into HLO metadata: the profiler locates
    # markers by label with ZERO runtime overhead — the gem5 PC-label
    # tracking analogue (paper §III-D2)
    with jax.named_scope("nugget_block_attn"):
        h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps, plus_one=plus_one)
        dt = x.dtype
        q, k, v = A.qkv(p["attn"], cfg.attn, dims.layout, h, positions, dt)
        ctx = A.attend(cfg.attention_impl, q, k, v, positions, positions,
                       dims.layout, causal=True, window=window,
                       cap=cfg.attn.softcap, q_chunk=cfg.attn_chunk,
                       kv_chunk=cfg.attn_chunk,
                       causal_skip=cfg.attn_causal_skip)
        return x + A.out_proj(p["attn"], dims.layout, ctx, dt), (k, v)


def _mla_attn_block(p, cfg: ArchConfig, dims: ModelDims, x, positions):
    """Latent attention over the whole sequence (decompressed); returns the
    residual stream and the layer's (c_kv, k_pe) for the cache."""
    with jax.named_scope("nugget_block_mla"):
        h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
        dt = x.dtype
        q_nope, q_pe, c_kv, k_pe = A.mla_project(p["attn"], cfg.attn, h,
                                                 positions, dt)
        ctx = A.mla_prefill(p["attn"], cfg.attn, dims.layout, q_nope, q_pe,
                            c_kv, k_pe, positions, dt,
                            impl=cfg.attention_impl, chunk=cfg.attn_chunk)
        return x + A.mla_out(p["attn"], ctx, dt), (c_kv, k_pe)


def _mlp_block(p, cfg, x, *, plus_one: bool, aux: Dict, rng=None):
    scope = "nugget_block_moe" if "moe" in p else "nugget_block_mlp"
    with jax.named_scope(scope):
        h = L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps, plus_one=plus_one)
        if "moe" in p:
            y, moe_aux = (M.moe_held(p["moe"], cfg, h)
                          if cfg.held_experts
                          else M.moe_mlp(p["moe"], cfg, h, rng=rng))
            for key, val in moe_aux.items():
                aux[key] = aux.get(key, 0) + val
        else:
            y = L.mlp(p["mlp"], h, cfg.act, x.dtype)
            y = shard(y, "batch", "seq", "act_embed")
        return x + y


def dense_layer(p, cfg, dims, x, positions, window, *, plus_one=False,
                aux=None, rng=None):
    aux = {} if aux is None else aux
    x, kv = _attn_block(p, cfg, dims, x, positions, window,
                        plus_one=plus_one, aux=aux)
    x = _mlp_block(p, cfg, x, plus_one=plus_one, aux=aux, rng=rng)
    return x, kv, aux


def take_layer(stack, i):
    """Layer ``i`` (traced) of a stacked subtree, read inside the loop."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stack)


def write_kda_states(c: Dict[str, Any], j, state, conv) -> None:
    """Write KDA layer ``j``'s state and convolution state into the stacked
    ``kda_state`` / ``kda_conv`` of ``c``, in place under a loop carry."""
    c["kda_state"] = jax.lax.dynamic_update_index_in_dim(
        c["kda_state"], state, j, 0)
    c["kda_conv"] = jax.lax.dynamic_update_index_in_dim(
        c["kda_conv"], conv.astype(c["kda_conv"].dtype), j, 0)


def _kda_block(stack, i, cfg: ArchConfig, x):
    """KDA layer ``i`` of ``stack`` over the whole sequence; returns the
    residual stream and the layer's final state and convolution state."""
    with jax.named_scope("nugget_block_kda"):
        p = take_layer({"attn_norm": stack["attn_norm"],
                        "kda": stack["kda"]}, i)
        h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
        y, state, conv = K.kda_prefill(p["kda"], cfg, h, x.dtype)
        return x + y, (state, conv)


def ssm_layer(p, cfg, x, *, aux=None):
    aux = {} if aux is None else aux
    with jax.named_scope("nugget_block_mamba"):
        h = L.rmsnorm(p["ssm_norm"], x, cfg.norm_eps)
        return x + S.mamba2_block(p["ssm"], cfg, h), aux


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


def _aux_zero(cfg: ArchConfig):
    aux = {}
    if cfg.family == "moe":
        aux["router_aux_loss"] = jnp.zeros((), jnp.float32)
        aux["router_logits_max"] = jnp.zeros((), jnp.float32)
        aux["expert_tokens"] = jnp.zeros((cfg.moe.n_experts,), jnp.int32)
        aux["dropped_tokens"] = jnp.zeros((), jnp.int32)
    if cfg.held_experts:
        aux["expert_tokens"] = jnp.zeros((cfg.moe.n_experts,), jnp.int32)
        aux["held_tokens"] = jnp.zeros((), jnp.int32)
        aux["dropped_tokens"] = jnp.zeros((), jnp.int32)
    return aux


def decoder_stack(params, cfg: ArchConfig, dims: ModelDims, x, positions,
                  *, collect_kv: bool = False, rng=None, plus_one=False):
    """Run all layers full-sequence.  Returns (x, aux, kv or None)."""
    windows = jnp.asarray(cfg.layer_windows() or [0], jnp.int32)
    aux0 = _aux_zero(cfg)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, xs):
            xc, aux = carry
            p, win, key = xs
            aux = dict(aux)
            xc, kv, aux = dense_layer(p, cfg, dims, xc, positions, win,
                                      plus_one=plus_one, aux=aux, rng=key)
            return (xc, aux), (kv if collect_kv else None)
        keys = (jax.random.split(rng, cfg.n_layers) if rng is not None
                else jnp.zeros((cfg.n_layers, 2), jnp.uint32))
        (x, aux), kv = jax.lax.scan(jax.checkpoint(body), (x, aux0),
                                    (params["layers"], windows, keys))
        return x, aux, kv

    if cfg.family == "mla_moe":
        return _mla_moe_stack(params, cfg, dims, x, positions,
                              collect_kv=collect_kv)

    if cfg.family == "kda_mla_moe":
        x, aux = kda_mla_moe_stack(params, cfg, dims, x, positions)
        return x, aux, None

    if cfg.family == "ssm":
        def body(carry, p):
            xc, aux = carry
            xc, aux = ssm_layer(p, cfg, xc, aux=dict(aux))
            return (xc, aux), None
        (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux0),
                                   params["layers"])
        return x, aux, None

    if cfg.family == "hybrid":
        return _hybrid_stack(params, cfg, dims, x, positions,
                             collect_kv=collect_kv)
    raise ValueError(cfg.family)


def _mla_moe_stack(params, cfg, dims, x, positions, *, collect_kv=False):
    """The leading dense layers, then the expert layers, each stack a scan;
    with ``collect_kv`` the (c_kv, k_pe) of every layer, stacked."""
    def body(carry, p):
        xc, aux = carry
        aux = dict(aux)
        xc, kv = _mla_attn_block(p, cfg, dims, xc, positions)
        xc = _mlp_block(p, cfg, xc, plus_one=False, aux=aux)
        return (xc, aux), (kv if collect_kv else None)
    body = jax.checkpoint(body)
    (x, aux), kv_d = jax.lax.scan(body, (x, _aux_zero(cfg)),
                                  params["dense_layers"])
    (x, aux), kv_m = jax.lax.scan(body, (x, aux), params["layers"])
    kv = (jax.tree.map(lambda a, b: jnp.concatenate([a, b]), kv_d, kv_m)
          if collect_kv else None)
    return x, aux, kv


def kda_mla_moe_stack(params, cfg: ArchConfig, dims: ModelDims, x,
                      positions, cache: Optional[Dict[str, Any]] = None):
    """The layers in schedule order over the whole sequence.  With
    ``cache`` (holding ``kda_state``, ``kda_conv``, ``c_kv``, ``k_pe``)
    each layer writes its entries into the stacked arrays carried through
    the scans, in place, and ``cache`` is updated; returns (x, aux)."""
    def layer(stack, kind, i, j, xc, aux, c):
        sp = params[stack]
        if kind == "kda":
            xc, (state, conv) = _kda_block(sp, i, cfg, xc)
            if c:
                write_kda_states(c, j, state, conv)
        else:
            xc, (c_kv, k_pe) = _mla_attn_block(
                take_layer({"attn_norm": sp["attn_norm"],
                            "attn": sp["attn"]}, i),
                cfg, dims, xc, positions)
            if c:
                for key, val in (("c_kv", c_kv), ("k_pe", k_pe)):
                    c[key] = jax.lax.dynamic_update_slice(
                        c[key], val[None].astype(c[key].dtype),
                        (j, 0, 0, 0))
        ffn = "moe" if "moe" in sp else "mlp"
        xc = _mlp_block(take_layer({"mlp_norm": sp["mlp_norm"],
                                    ffn: sp[ffn]}, i),
                        cfg, xc, plus_one=False, aux=aux)
        return xc, aux, c

    return run_plan(cfg, layer, x, _aux_zero(cfg),
                    {} if cache is None else cache, remat=cache is None)


def _hybrid_groups(cfg: ArchConfig):
    ae = max(cfg.attn_every, 1)
    n_groups = cfg.n_layers // ae
    remainder = cfg.n_layers - n_groups * ae
    return ae, n_groups, remainder


def _shared_attn_block(params, cfg, dims, x, positions, *, cache_kv=None,
                       cache_len=None, collect_kv=False):
    p = params["shared_attn"]
    h = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    dt = x.dtype
    q, k, v = A.qkv(p["attn"], cfg.attn, dims.layout, h, positions, dt)
    win = jnp.int32(-1)
    if cache_kv is not None:
        kc, vc = cache_kv
        ctx = A.attend_decode(q, kc, vc, cache_len, dims.layout, window=win)
    else:
        ctx = A.attend(cfg.attention_impl, q, k, v, positions, positions,
                       dims.layout, causal=True, window=win,
                       q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
    x = x + A.out_proj(p["attn"], dims.layout, ctx, dt)
    h = L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + shard(L.mlp(p["mlp"], h, cfg.act, dt), "batch", "seq", "act_embed")
    return x, (k, v) if collect_kv else None


def _hybrid_stack(params, cfg, dims, x, positions, *, collect_kv=False):
    ae, n_groups, rem = _hybrid_groups(cfg)
    aux = _aux_zero(cfg)

    def ssm_body(carry, p):
        xc = carry
        xc, _ = ssm_layer(p, cfg, xc)
        return xc, None
    ssm_body = jax.checkpoint(ssm_body)

    kvs = []
    for g in range(n_groups):
        sl = jax.tree.map(lambda a: a[g * ae:(g + 1) * ae], params["layers"])
        x, _ = jax.lax.scan(ssm_body, x, sl)
        x, kv = _shared_attn_block(params, cfg, dims, x, positions,
                                   collect_kv=collect_kv)
        kvs.append(kv)
    if rem:
        sl = jax.tree.map(lambda a: a[n_groups * ae:], params["layers"])
        x, _ = jax.lax.scan(ssm_body, x, sl)
    kv = (jax.tree.map(lambda *a: jnp.stack(a), *kvs) if collect_kv else None)
    return x, aux, kv


# ---------------------------------------------------------------------------
# Top-level model
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg: ArchConfig, dims: ModelDims, tokens,
                 patch_embeds=None):
    dt = dtype_of(cfg.compute_dtype)
    x = L.embed_lookup(params["embed"], tokens, dt)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    if cfg.n_patches and patch_embeds is not None:
        pe = L.dense(params["patch_proj"], patch_embeds.astype(dt), dt)
        x = jnp.concatenate([pe, x[:, cfg.n_patches:]], axis=1) \
            if x.shape[1] > cfg.n_patches else pe[:, :x.shape[1]]
    return shard(x, "batch", "seq", "act_embed")


def unembed(params, cfg: ArchConfig, dims: ModelDims, x):
    dt = dtype_of(cfg.compute_dtype)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x, dt)
    else:
        logits = L.dense(params["lm_head"], x, dt)
    logits = shard(logits, "batch", "seq", "act_vocab")
    if dims.vocab_pad > cfg.vocab_size:
        mask = (jnp.arange(dims.vocab_pad) < cfg.vocab_size)
        logits = jnp.where(mask[None, None], logits, -1e30)
    return logits


def lm_forward(params, cfg: ArchConfig, dims: ModelDims, tokens,
               *, patch_embeds=None, rng=None) -> Tuple[jax.Array, Dict]:
    """Training/prefill forward over full sequences -> (logits, aux)."""
    plus_one = cfg.name.startswith("gemma")
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed_tokens(params, cfg, dims, tokens, patch_embeds)
    x, aux, _ = decoder_stack(params, cfg, dims, x, positions, rng=rng,
                              plus_one=plus_one)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps, plus_one=plus_one)
    return unembed(params, cfg, dims, x), aux
