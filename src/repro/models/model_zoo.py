"""Unified model facade: build any architecture, expose init / loss /
forward / prefill / decode plus cache construction.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, dtype_of
from repro.distributed.sharding import ShardingPlan
from repro.models import decode as D
from repro.models import encdec as ED
from repro.models import kvcache as KC
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.transformer import ModelDims


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_size: int, *, z_loss: float = 1e-4):
    """Sharded-vocab-safe CE with z-loss.  logits: [B,S,V], labels: [B,S]."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    shifted = lf - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=jnp.float32)
    correct = jnp.sum(lf * onehot, axis=-1)
    nll = lse - correct
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss, nll


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    dims: ModelDims

    # ---- params ----------------------------------------------------------
    def specs(self):
        if self.cfg.family == "encdec":
            return ED.encdec_specs(self.cfg, self.dims)
        return T.lm_specs(self.cfg, self.dims)

    def init(self, key: jax.Array):
        return L.init_tree(key, self.specs(), dtype_of(self.cfg.param_dtype))

    def axes(self):
        return L.axes_tree(self.specs())

    # ---- forward ---------------------------------------------------------
    def forward(self, params, batch: Dict[str, jax.Array], *, rng=None):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ED.encdec_forward(params, cfg, self.dims,
                                     batch["tokens"], batch["frames"])
        return T.lm_forward(params, cfg, self.dims, batch["tokens"],
                            patch_embeds=batch.get("patches"), rng=rng)

    def loss(self, params, batch, *, rng=None):
        logits, aux = self.forward(params, batch, rng=rng)
        loss, nll = cross_entropy(logits, batch["labels"], self.cfg.vocab_size)
        if "router_aux_loss" in aux:
            loss = loss + aux["router_aux_loss"] / max(self.cfg.n_layers, 1)
        aux["nll_mean"] = jnp.mean(nll)
        return loss, aux

    # ---- serving ---------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        dt = dtype_of(cfg.compute_dtype)
        kv_pad = self.dims.layout.kv_pad if self.dims.layout else 0
        hd = cfg.attn.head_dim if cfg.attn else 0
        ssm = None
        if cfg.family in ("ssm", "hybrid"):
            d_inner, nh = S.ssm_dims(cfg)
            ssm = dict(n_layers=cfg.n_layers, n_heads=nh,
                       head_dim=cfg.ssm.head_dim, d_state=cfg.ssm.d_state,
                       d_conv=cfg.ssm.d_conv, conv_dim=S.conv_dim(cfg))
        if cfg.family == "ssm":
            return KC.init_cache(cfg.n_layers, batch, max_seq, 0, 0, dt, ssm=ssm)
        if cfg.family == "mla_moe":
            mla = cfg.attn.mla
            return KC.init_cache(cfg.n_layers, batch, max_seq, 0, 0, dt,
                                 mla=(mla.kv_lora_rank, mla.rope_dim))
        if cfg.family == "hybrid":
            ae, n_groups, _ = T._hybrid_groups(cfg)
            return KC.init_cache(n_groups, batch, max_seq, kv_pad, hd, dt,
                                 ssm=ssm)
        if cfg.family == "encdec":
            return KC.init_cache(cfg.n_layers, batch, max_seq, kv_pad, hd, dt,
                                 cross_len=cfg.n_frames)
        return KC.init_cache(cfg.n_layers, batch, max_seq, kv_pad, hd, dt)

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ED.encdec_prefill(params, cfg, self.dims, batch["tokens"],
                                     batch["frames"], cache)
        return D.lm_prefill(params, cfg, self.dims, batch["tokens"], cache,
                            patch_embeds=batch.get("patches"))

    def decode_step(self, params, token, cache):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ED.encdec_decode(params, cfg, self.dims, token, cache)
        return D.lm_decode(params, cfg, self.dims, token, cache)

    def param_count(self, params=None) -> int:
        if params is not None:
            return L.param_count(params)
        return self.cfg.param_count()


def build_model(cfg: ArchConfig, plan: Optional[ShardingPlan] = None) -> Model:
    tp = plan.tp_size if plan is not None else 1
    return Model(cfg, ModelDims.make(cfg, tp))
