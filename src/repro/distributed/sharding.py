"""Logical-axis → mesh-axis sharding rules.

Model code annotates params and activations with *logical* axis names; the
active :class:`ShardingPlan` maps those to mesh axes.  Rules differ between
training (2D FSDP×TP) and serving (TP, batch-sharded KV).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Resolved mapping from logical axes to mesh axes."""
    rules: Tuple[Tuple[str, Any], ...]     # logical -> mesh axis (or tuple / None)
    tp_size: int                           # size of the tensor axis (1 = TP off)
    dp_axes: Tuple[str, ...]               # batch/FSDP mesh axes
    tp_axis: Optional[str]                 # tensor mesh axis name

    def lookup(self, logical: Optional[str]):
        if logical is None:
            return None
        for k, v in self.rules:
            if k == logical:
                return v
        return None

    def spec(self, axes: Sequence[Optional[str]]) -> P:
        resolved, used = [], set()
        for a in axes:
            v = self.lookup(a)
            # a mesh axis may appear at most once in a PartitionSpec
            flat = v if isinstance(v, tuple) else ((v,) if v else ())
            if any(m in used for m in flat):
                v = None
            else:
                used.update(flat)
            resolved.append(v)
        return P(*resolved)


def _mk(rules: Dict[str, Any], tp_size: int, dp_axes, tp_axis) -> ShardingPlan:
    return ShardingPlan(tuple(rules.items()), tp_size, tuple(dp_axes), tp_axis)


def logical_rules(mesh: Mesh, *, mode: str = "train") -> ShardingPlan:
    """Build the sharding plan for a mesh.

    mode="train":  batch over (pod?,data); params 2D: FSDP("data") × TP("model").
    mode="serve":  params TP only (replicated over data); batch over (pod?,data).
    """
    names = mesh.axis_names
    pod = "pod" if "pod" in names else None
    data = "data" if "data" in names else None
    model = "model" if "model" in names else None
    batch_axes = tuple(a for a in (pod, data) if a)
    batch = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    fsdp = data if mode == "train" else None
    tp_size = int(mesh.shape["model"]) if model else 1

    rules: Dict[str, Any] = {
        "batch": batch,
        "embed": fsdp,
        "mlp": model,
        "heads": model,
        "kv_heads": model,
        "head_dim": None,
        "vocab": model,
        "layer": None,
        "experts": model,
        "expert_mlp": None,
        "ssm_inner": model,
        "ssm_state": None,
        "conv": None,
        "act_embed": None,        # activation d_model dim
        "act_heads": model,       # activation head dim
        "act_vocab": model,       # logits vocab dim
        "kv_seq": None,
        "seq": None,
    }
    return _mk(rules, tp_size, batch_axes, model)


# --------------------------------------------------------------------------
# Active-plan context: model code calls shard(x, *logical_axes); it is a
# no-op unless a plan is active (tests / single-device runs).
# --------------------------------------------------------------------------

_STATE = threading.local()


def set_rules(plan: Optional[ShardingPlan]):
    _STATE.plan = plan


def active_rules() -> Optional[ShardingPlan]:
    return getattr(_STATE, "plan", None)


@contextlib.contextmanager
def use_rules(plan: Optional[ShardingPlan]):
    prev = active_rules()
    set_rules(plan)
    try:
        yield
    finally:
        set_rules(prev)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    plan = active_rules()
    if plan is None:
        return x
    spec = plan.spec(axes)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def spec_for(axes: Sequence[Optional[str]]) -> P:
    plan = active_rules()
    if plan is None:
        return P()
    return plan.spec(axes)


def params_shardings(mesh: Mesh, plan: ShardingPlan, axes_tree) -> Any:
    """Map an axes pytree (tuples of logical names) to NamedShardings."""
    def _one(axes):
        return NamedSharding(mesh, plan.spec(axes))
    return jax.tree.map(_one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))
