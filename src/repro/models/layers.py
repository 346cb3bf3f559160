"""Parameter machinery + elementary layers (pure JAX, no flax).

Parameters are plain pytrees (nested dicts of jnp arrays).  Every leaf is
declared through a :class:`ParamSpec` carrying *logical axis names*; a
parallel tree of logical-axes tuples is produced at init and mapped to mesh
``PartitionSpec`` s by :mod:`repro.distributed.sharding` rules.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
Axes = Dict[str, Any]

# ---------------------------------------------------------------------------
# Param spec / initialisation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | scaled | custom
    scale: float = 1.0
    init_fn: Optional[Callable[[jax.Array, Tuple[int, ...]], jax.Array]] = None

    def instantiate(self, key: jax.Array, dtype) -> jax.Array:
        if self.init_fn is not None:
            return self.init_fn(key, self.shape).astype(dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "scaled":
            fan_in = self.shape[0] if self.shape else 1
            std = self.scale / math.sqrt(max(fan_in, 1))
            return (std * jax.random.normal(key, self.shape)).astype(dtype)
        return (self.scale * 0.02 * jax.random.normal(key, self.shape)).astype(dtype)


def init_tree(key: jax.Array, specs: Dict[str, Any], dtype) -> Params:
    """Instantiate a (nested) dict of ParamSpec into arrays."""
    flat, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(flat))
    leaves = [s.instantiate(k, dtype) for s, k in zip(flat, keys)]
    return jax.tree.unflatten(treedef, leaves)


def axes_tree(specs: Dict[str, Any]) -> Axes:
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_specs(specs: Dict[str, Any], n: int, axis_name: str = "layer") -> Dict[str, Any]:
    """Add a leading stacked-layer dimension to every spec (for scanned layers)."""
    def _stack(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(s, shape=(n,) + s.shape,
                                   axes=(axis_name,) + s.axes)
    return jax.tree.map(_stack, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_specs(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), "ones")}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6,
            *, plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = params["scale"].astype(jnp.float32)
    if plus_one:                       # gemma-style (1 + scale)
        scale = 1.0 + scale
    return (y * scale).astype(dt)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt)


# ---------------------------------------------------------------------------
# Projections / embeddings / MLP
# ---------------------------------------------------------------------------


def dense_specs(d_in: int, d_out: int, axes: Tuple[Optional[str], ...],
                *, bias: bool = False, init: str = "scaled",
                scale: float = 1.0) -> Dict[str, ParamSpec]:
    out = {"kernel": ParamSpec((d_in, d_out), axes, init, scale)}
    if bias:
        out["bias"] = ParamSpec((d_out,), (axes[-1],), "zeros")
    return out


def dense(params: Params, x: jax.Array, compute_dtype=None) -> jax.Array:
    if compute_dtype is None:
        compute_dtype = x.dtype
    y = x.astype(compute_dtype) @ params["kernel"].astype(compute_dtype)
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


def embed_specs(vocab: int, d: int) -> Dict[str, ParamSpec]:
    return {"embedding": ParamSpec((vocab, d), ("vocab", "embed"), "normal", 1.0)}


def embed_lookup(params: Params, tokens: jax.Array, compute_dtype) -> jax.Array:
    # one-hot matmul keeps the op MXU-friendly AND shardable over "vocab";
    # take() would force a replicated gather of the sharded table.
    emb = params["embedding"]
    return emb.astype(compute_dtype)[tokens]


def unembed(params: Params, x: jax.Array, compute_dtype) -> jax.Array:
    emb = params["embedding"].astype(compute_dtype)
    return x.astype(compute_dtype) @ emb.T


ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def mlp_specs(d: int, f: int, *, glu: bool = True) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "wi": dense_specs(d, f, ("embed", "mlp")),
        "wo": dense_specs(f, d, ("mlp", "embed")),
    }
    if glu:
        specs["wg"] = dense_specs(d, f, ("embed", "mlp"))
    return specs


def mlp(params: Params, x: jax.Array, act: str, compute_dtype) -> jax.Array:
    h = dense(params["wi"], x, compute_dtype)
    h = ACTS[act](h)
    if "wg" in params:
        h = h * dense(params["wg"], x, compute_dtype)
    return dense(params["wo"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    angles = angles[..., None, :]                              # head axis
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float) -> jax.Array:
    """DeepSeek's convention: rotates the pairs ``(x[2i], x[2i+1])`` by
    ``position · theta^(-2i/hd)``, in place.  (DeepSeek's modelling code
    also moves the rotated pairs to halves; q and k move alike, so their
    dot products, all that attention reads, are the same.)"""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], hd // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)
