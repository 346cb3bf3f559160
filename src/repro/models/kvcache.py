"""KV cache (decoder self-attention) + recurrent SSM state.

Layout: stacked over layers; decode carries the attention caches (and
KDA's states) through its layer scan and scans the SSM state as scan xs/ys.
``k``/``v``: [L, B, S_max, KVp, hd]; latent attention (MLA) holds no
per-head keys or values but ``c_kv`` [L, B, S_max, rank] and ``k_pe`` [L,
B, S_max, rope]; SSM state: [L, B, nh, hd, N] and conv state [L, B,
d_conv-1, d_conv_dim].  Kimi Linear keeps both kinds side by side, each
stacked over its own layers: ``c_kv``/``k_pe`` over the latent layers, and
over the KDA layers the float32 state ``kda_state`` [L_kda, B, H, D, D] and
``kda_conv`` [L_kda, B, d_conv-1, 3HD], neither growing with the sequence.
Sharding: batch over ("pod","data"), heads over "model".
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.distributed.sharding import shard

CACHE_AXES = {
    "k": (None, "batch", "kv_seq", "act_heads", None),
    "v": (None, "batch", "kv_seq", "act_heads", None),
    "c_kv": (None, "batch", "kv_seq", None),
    "k_pe": (None, "batch", "kv_seq", None),
    "cross_k": (None, "batch", None, "act_heads", None),
    "cross_v": (None, "batch", None, "act_heads", None),
    "ssm": (None, "batch", "act_heads", None, None),
    "conv": (None, "batch", None, "ssm_inner"),
    "kda_state": (None, "batch", "act_heads", None, None),
    "kda_conv": (None, "batch", None, None),
    "length": ("batch",),
}
# the cache arrays of each kind of layer of the ``kda_mla_moe`` family
KIND_KEYS = {"kda": ("kda_state", "kda_conv"), "mla": ("c_kv", "k_pe")}


def init_cache(n_layers: int, batch: int, max_seq: int, kv_pad: int,
               head_dim: int, dtype, *, ssm: Optional[Dict[str, int]] = None,
               cross_len: int = 0,
               mla: Optional[Tuple[int, int]] = None,
               kda: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
    """``mla``: (latent rank, rope width) of a latent-attention cache, which
    then holds ``c_kv`` and ``k_pe`` in place of ``k`` and ``v``.  ``kda``:
    ``n_layers``, ``n_heads``, ``head_dim``, ``d_conv`` and ``conv_dim`` of
    KDA's states beside it."""
    cache: Dict[str, Any] = {
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if mla is not None:
        rank, rope = mla
        cache["c_kv"] = jnp.zeros((n_layers, batch, max_seq, rank), dtype)
        cache["k_pe"] = jnp.zeros((n_layers, batch, max_seq, rope), dtype)
    if kv_pad:
        cache["k"] = jnp.zeros((n_layers, batch, max_seq, kv_pad, head_dim),
                               dtype)
        cache["v"] = jnp.zeros((n_layers, batch, max_seq, kv_pad, head_dim),
                               dtype)
    if cross_len and kv_pad:
        cache["cross_k"] = jnp.zeros((n_layers, batch, cross_len, kv_pad, head_dim), dtype)
        cache["cross_v"] = jnp.zeros((n_layers, batch, cross_len, kv_pad, head_dim), dtype)
    if kda is not None:
        d = kda["head_dim"]
        cache["kda_state"] = jnp.zeros(
            (kda["n_layers"], batch, kda["n_heads"], d, d), jnp.float32)
        cache["kda_conv"] = jnp.zeros(
            (kda["n_layers"], batch, kda["d_conv"] - 1, kda["conv_dim"]),
            dtype)
    if ssm is not None:
        cache["ssm"] = jnp.zeros(
            (ssm["n_layers"], batch, ssm["n_heads"], ssm["head_dim"], ssm["d_state"]),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (ssm["n_layers"], batch, ssm["d_conv"] - 1, ssm["conv_dim"]), dtype)
    return cache


def shard_cache(cache: Dict[str, Any]) -> Dict[str, Any]:
    return {k: shard(v, *CACHE_AXES[k]) for k, v in cache.items()}
