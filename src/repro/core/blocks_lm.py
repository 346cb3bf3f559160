"""Per-architecture BlockTable construction (the "interval analysis pass").

This is the analogue of the paper's LLVM pass walking the IR: we trace each
model block once (ShapeDtypeStruct inputs, no allocation), record its jaxpr
op count as the block's IR size, and lay out the step's hook-stream program.
Training steps scale block costs by the traced grad/fwd ratio so the unit of
work covers the whole executed step (forward hook positions, DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig, dtype_of
from repro.core.registry import BlockDef, BlockTable, Segment
from repro.core.unit_of_work import IRCost, struct_like, trace_cost
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.model_zoo import Model, build_model, cross_entropy


def _spec_struct(specs, dtype):
    """ParamSpec tree -> ShapeDtypeStruct tree (zero-cost tracing inputs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))


def _x_struct(b, s, d, dtype):
    return jax.ShapeDtypeStruct((b, s, d), dtype)


def build_block_table(model: Model, shape: ShapeConfig,
                      *, train: bool = True, unit: str = "ops") -> BlockTable:
    """``unit``: "ops" counts executed jaxpr equations (the default,
    LLVM-IR-instruction analogue; exact for homogeneous step streams);
    "flops" weighs each block by its traced FLOPs — the pluggable
    unit-of-work choice (paper §III-A) needed when steps are heterogeneous
    in tensor volume (serving: a 16-token prefill must out-weigh a 1-token
    decode even though both lower to the same number of jaxpr ops)."""
    cfg = model.cfg
    dims = model.dims
    dt = dtype_of(cfg.compute_dtype)
    b = max(shape.global_batch, 1)
    s = shape.seq_len if shape.kind != "decode" else 1
    d = cfg.d_model
    x = _x_struct(b, s, d, dt)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32)
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)

    layer_sp = (T.layer_specs(cfg, dims)
                if cfg.family not in ("encdec", "kda_mla_moe") else None)
    lp = _spec_struct(layer_sp, dt) if layer_sp is not None else None

    blocks: List[BlockDef] = []
    prog: List[Segment] = []

    def add(name: str, cost: IRCost, **kw) -> int:
        blocks.append(BlockDef(name, cost.ops, cost.flops, **kw))
        return len(blocks) - 1

    # ---- embed -----------------------------------------------------------
    emb_sp = {"embedding": jax.ShapeDtypeStruct((dims.vocab_pad, d), dt)}
    c_embed = trace_cost(lambda p, t: L.embed_lookup(p, t, dt), emb_sp, toks)
    i_embed = add("embed", c_embed)
    prog.append(Segment((i_embed,), 1))

    # ---- per-layer blocks --------------------------------------------------
    if cfg.family == "mla_moe":
        from repro.models import moe as M
        dense_lp = _spec_struct(T.mla_layer_specs(cfg, moe=False), dt)
        c_attn = trace_cost(
            lambda p, xx, pp: T._mla_attn_block(p, cfg, dims, xx, pp)[0],
            lp, x, pos)
        i_attn = add("mla", c_attn)
        c_mlp = trace_cost(
            lambda p, xx: T._mlp_block(p, cfg, xx, plus_one=False,
                                       aux=T._aux_zero(cfg)), dense_lp, x)
        i_mlp = add("mlp", c_mlp)
        c_moe = trace_cost(
            lambda p, xx: M.moe_held(p["moe"], cfg, xx)[0], lp, x)
        i_moe = add("moe", c_moe)
        prog.append(Segment((i_attn, i_mlp), cfg.first_k_dense))
        prog.append(Segment((i_attn, i_moe),
                            cfg.n_layers - cfg.first_k_dense))

    elif cfg.family == "kda_mla_moe":
        prog += _kda_mla_moe_segments(cfg, dims, shape, x, pos, dt, add)

    elif cfg.family in ("dense", "moe", "vlm"):
        win = jnp.int32(-1)
        c_attn = trace_cost(
            lambda p, xx, pp: T._attn_block(p, cfg, dims, xx, pp, win,
                                            plus_one=False, aux={})[0],
            lp, x, pos)
        i_attn = add("attn", c_attn)
        if cfg.family == "moe":
            from repro.models import moe as M
            c_moe = trace_cost(
                lambda p, xx: M.moe_mlp(p["moe"], cfg, xx)[0], lp, x)
            i_mlp = add("moe", c_moe)
        else:
            c_mlp = trace_cost(
                lambda p, xx: T._mlp_block(p, cfg, xx, plus_one=False,
                                           aux={}), lp, x)
            i_mlp = add("mlp", c_mlp)
        prog.append(Segment((i_attn, i_mlp), cfg.n_layers))

    elif cfg.family == "ssm":
        c_ssm = trace_cost(
            lambda p, xx: T.ssm_layer(p, cfg, xx)[0], lp, x)
        i_ssm = add("mamba", c_ssm)
        prog.append(Segment((i_ssm,), cfg.n_layers))

    elif cfg.family == "hybrid":
        c_ssm = trace_cost(lambda p, xx: T.ssm_layer(p, cfg, xx)[0], lp, x)
        i_ssm = add("mamba", c_ssm)
        sh_sp = _spec_struct(T.shared_attn_specs(cfg, dims), dt)
        c_sh = trace_cost(
            lambda p, xx, pp: T._shared_attn_block(
                {"shared_attn": p}, cfg, dims, xx, pp)[0], sh_sp, x, pos)
        i_sh = add("shared_attn", c_sh)
        ae, n_groups, rem = T._hybrid_groups(cfg)
        for g in range(n_groups):
            prog.append(Segment((i_ssm,), ae))
            prog.append(Segment((i_sh,), 1))
        if rem:
            prog.append(Segment((i_ssm,), rem))

    elif cfg.family == "encdec":
        from repro.models import encdec as ED
        enc_sp = _spec_struct(ED._enc_layer_specs(cfg, dims), dt)
        dec_sp = _spec_struct(ED._dec_layer_specs(cfg, dims), dt)
        xe = _x_struct(b, cfg.n_frames, d, dt)
        pe = jax.ShapeDtypeStruct((b, cfg.n_frames), jnp.int32)

        def enc_body(p, xx, pp):
            h = ED.layernorm(p["attn_norm"], xx)
            y, _ = ED._self_attn(p["attn"], cfg, dims, h, pp, causal=False, dt=dt)
            xx = xx + y
            h = ED.layernorm(p["mlp_norm"], xx)
            return xx + L.mlp(p["mlp"], h, "gelu", dt)
        c_enc = trace_cost(enc_body, enc_sp, xe, pe)
        i_enc = add("enc_layer", c_enc)

        enc_out = xe

        def dec_body(p, xx, pp, eo):
            h = ED.layernorm(p["attn_norm"], xx)
            y, _ = ED._self_attn(p["attn"], cfg, dims, h, pp, causal=True, dt=dt)
            xx = xx + y
            h = ED.layernorm(p["xattn_norm"], xx)
            k, v = ED._cross_kv(p["xattn"], cfg, dims, eo, dt)
            xx = xx + ED._cross_attend(p["xattn"], cfg, dims, h, k, v, dt)
            h = ED.layernorm(p["mlp_norm"], xx)
            return xx + L.mlp(p["mlp"], h, "gelu", dt)
        c_dec = trace_cost(dec_body, dec_sp, x, pos, enc_out)
        i_dec = add("dec_layer", c_dec)
        prog.append(Segment((i_enc,), cfg.n_enc_layers))
        prog.append(Segment((i_dec,), cfg.n_layers))

    # ---- head (final norm + unembed + loss) --------------------------------
    def head_fn(p, xx, lbl):
        h = L.rmsnorm(p["norm"], xx, cfg.norm_eps)
        logits = h.astype(dt) @ p["head"]
        return cross_entropy(logits, lbl, cfg.vocab_size)[0]
    head_sp = {"norm": {"scale": jax.ShapeDtypeStruct((d,), dt)},
               "head": jax.ShapeDtypeStruct((d, dims.vocab_pad), dt)}
    c_head = trace_cost(head_fn, head_sp, x, toks)
    i_head = add("head", c_head)
    prog.append(Segment((i_head,), 1))

    # ---- virtual (signature-only) blocks -----------------------------------
    # one entry per expert the router scores, held here or not
    if cfg.family == "moe" or cfg.held_experts:
        for e in range(cfg.moe.n_experts):
            add(f"expert_tok_{e}", IRCost(0, 0, 0), virtual=True,
                dyn_key="expert_tokens", dyn_index=e)
        add("dropped_tokens", IRCost(0, 0, 0), virtual=True,
            dyn_key="dropped_tokens")

    if unit == "flops":
        blocks = [dataclasses.replace(
            bl, cost_ops=max(1.0, bl.cost_flops)) for bl in blocks]
    table = BlockTable(blocks, prog)

    # ---- train-step scaling (fwd+bwd+optimizer coverage) -------------------
    if train and shape.kind == "train":
        scale = _train_scale(model, shape)
        table = BlockTable(
            [dataclasses.replace(bl, cost_ops=bl.cost_ops * scale,
                                 cost_flops=bl.cost_flops * scale)
             for bl in table.blocks], table.program)
    return table


def _kda_mla_moe_segments(cfg, dims, shape, x, pos, dt, add):
    """One segment per run of the layer schedule, with a block per
    attention kind and per feed-forward kind.  A KDA block is costed by the
    work the step runs: the chunked recurrence over the sequence, or, in a
    decode, one step of every row's state."""
    from repro.models import kda as K
    from repro.models import moe as M
    # a layer of each kind (attention "kda"/"mla", feed-forward "mlp"/"moe")
    plan = T.layer_plan(cfg)
    stack = {}
    for _, layers in plan:
        for st, *_ in layers:
            for part in st.split("_")[1:]:
                stack.setdefault(part, _spec_struct(
                    T.kda_layer_specs(cfg, st.split("_", 1)[1]), dt))
    if shape.kind == "decode":
        k = cfg.kda
        state = jax.ShapeDtypeStruct(
            (x.shape[0], k.n_heads, k.head_dim, k.head_dim), jnp.float32)
        conv = jax.ShapeDtypeStruct(
            (x.shape[0], k.d_conv - 1, K.conv_dim(cfg)), dt)

        def kda(p, xx, st, cv):
            h = L.rmsnorm(p["attn_norm"], xx, cfg.norm_eps)
            return K.kda_decode(p["kda"], cfg, h, st, cv, dt)[0]
        kda_args = (state, conv)
    else:
        def kda(p, xx):
            return T._kda_block(jax.tree.map(lambda a: a[None], p),
                                jnp.int32(0), cfg, xx)[0]
        kda_args = ()
    costs = {
        "kda": lambda: trace_cost(kda, stack["kda"], x, *kda_args),
        "mla": lambda: trace_cost(
            lambda p, xx, pp: T._mla_attn_block(p, cfg, dims, xx, pp)[0],
            stack["mla"], x, pos),
        "mlp": lambda: trace_cost(
            lambda p, xx: T._mlp_block(p, cfg, xx, plus_one=False,
                                       aux=T._aux_zero(cfg)),
            stack["mlp"], x),
        "moe": lambda: trace_cost(
            lambda p, xx: M.moe_held(p["moe"], cfg, xx)[0], stack["moe"], x),
    }
    blocks = {part: add(part, cost()) for part, cost in costs.items()
              if part in stack}
    return [Segment(tuple(blocks[part] for st, *_ in layers
                          for part in st.split("_")[1:]), reps)
            for reps, layers in plan]


@functools.lru_cache(maxsize=32)
def _train_scale_cached(name: str, seq: int, batch: int) -> float:
    return 3.0


def _train_scale(model: Model, shape: ShapeConfig) -> float:
    """Traced grad/fwd IR-op ratio on a reduced clone (cheap, cached)."""
    try:
        from repro.configs.base import reduced
        cfg_r = reduced(model.cfg)
        m_r = build_model(cfg_r)
        key = jax.random.PRNGKey(0)
        sp = _spec_struct(m_r.specs(), dtype_of(cfg_r.param_dtype))
        toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        if cfg_r.family == "encdec":
            batch["frames"] = jax.ShapeDtypeStruct(
                (2, cfg_r.n_frames, cfg_r.d_model), jnp.float32)
        if cfg_r.n_patches:
            batch["patches"] = jax.ShapeDtypeStruct(
                (2, cfg_r.n_patches, cfg_r.d_model), jnp.float32)
        fwd = trace_cost(lambda p: m_r.loss(p, batch)[0], sp)
        bwd = trace_cost(
            lambda p: jax.grad(lambda q: m_r.loss(q, batch)[0])(p), sp)
        return max(1.0, bwd.ops / max(fwd.ops, 1.0))
    except Exception:
        return 3.0
