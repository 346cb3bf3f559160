"""KV cache (decoder self-attention) + recurrent SSM state.

Layout: stacked over layers; decode carries the attention caches through its
layer scan and scans the SSM state as scan xs/ys.  ``k``/``v``: [L, B,
S_max, KVp, hd]; latent attention (MLA) holds no per-head keys or values but
``c_kv`` [L, B, S_max, rank] and ``k_pe`` [L, B, S_max, rope]; SSM state:
[L, B, nh, hd, N] and conv state [L, B, d_conv-1, d_conv_dim].  Sharding:
batch over ("pod","data"), heads over "model"; for long-context (batch=1)
the sequence dim is sharded over "data" instead (see ShardingPlan.kv_seq).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard

CACHE_AXES = {
    "k": (None, "batch", "kv_seq", "act_heads", None),
    "v": (None, "batch", "kv_seq", "act_heads", None),
    "k_scale": (None, "batch", "kv_seq", "act_heads"),
    "v_scale": (None, "batch", "kv_seq", "act_heads"),
    "c_kv": (None, "batch", "kv_seq", None),
    "k_pe": (None, "batch", "kv_seq", None),
    "cross_k": (None, "batch", None, "act_heads", None),
    "cross_v": (None, "batch", None, "act_heads", None),
    "ssm": (None, "batch", "act_heads", None, None),
    "conv": (None, "batch", None, "ssm_inner"),
    "length": ("batch",),
}


def quantize_kv(x: jax.Array):
    """Per-(token, head) int8 quantization.  x: [..., hd] ->
    (int8 [..., hd], scale [...] bf16 with the /127 folded in)."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(m, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def init_cache(n_layers: int, batch: int, max_seq: int, kv_pad: int,
               head_dim: int, dtype, *, ssm: Optional[Dict[str, int]] = None,
               cross_len: int = 0, quant: bool = False,
               mla: Optional[Tuple[int, int]] = None) -> Dict[str, Any]:
    """``mla``: (latent rank, rope width) of a latent-attention cache, which
    then holds ``c_kv`` and ``k_pe`` in place of ``k`` and ``v``."""
    cache: Dict[str, Any] = {
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if mla is not None:
        rank, rope = mla
        cache["c_kv"] = jnp.zeros((n_layers, batch, max_seq, rank), dtype)
        cache["k_pe"] = jnp.zeros((n_layers, batch, max_seq, rope), dtype)
    kv_dtype = jnp.int8 if quant else dtype
    if kv_pad:
        cache["k"] = jnp.zeros((n_layers, batch, max_seq, kv_pad, head_dim),
                               kv_dtype)
        cache["v"] = jnp.zeros((n_layers, batch, max_seq, kv_pad, head_dim),
                               kv_dtype)
        if quant:
            cache["k_scale"] = jnp.zeros((n_layers, batch, max_seq, kv_pad),
                                         jnp.bfloat16)
            cache["v_scale"] = jnp.zeros((n_layers, batch, max_seq, kv_pad),
                                         jnp.bfloat16)
    if cross_len and kv_pad:
        cache["cross_k"] = jnp.zeros((n_layers, batch, cross_len, kv_pad, head_dim), dtype)
        cache["cross_v"] = jnp.zeros((n_layers, batch, cross_len, kv_pad, head_dim), dtype)
    if ssm is not None:
        cache["ssm"] = jnp.zeros(
            (ssm["n_layers"], batch, ssm["n_heads"], ssm["head_dim"], ssm["d_state"]),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (ssm["n_layers"], batch, ssm["d_conv"] - 1, ssm["conv_dim"]), dtype)
    return cache


def shard_cache(cache: Dict[str, Any]) -> Dict[str, Any]:
    return {k: shard(v, *CACHE_AXES[k]) for k, v in cache.items()}


def cache_specs(cache: Dict[str, Any], plan) -> Dict[str, Any]:
    return {k: plan.spec(CACHE_AXES[k]) for k in cache}


def update_layer_kv(k_layer: jax.Array, v_layer: jax.Array,
                    k_new: jax.Array, v_new: jax.Array,
                    index: jax.Array):
    """Write k_new/v_new ([B,s,KVp,hd]) at position ``index`` (scalar)."""
    k_layer = jax.lax.dynamic_update_slice(
        k_layer, k_new.astype(k_layer.dtype), (0, index, 0, 0))
    v_layer = jax.lax.dynamic_update_slice(
        v_layer, v_new.astype(v_layer.dtype), (0, index, 0, 0))
    return (shard(k_layer, "batch", "kv_seq", "act_heads", None),
            shard(v_layer, "batch", "kv_seq", "act_heads", None))
