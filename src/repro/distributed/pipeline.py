"""GPipe-style pipeline parallelism over a "stage" mesh axis.

The production meshes in this assignment are (data, model)-shaped, so PP is
an *optional* extra dimension for deployments that prefer pipelining over
FSDP for very deep models (88-layer mistral at low batch). Implementation:
shard_map over the stage axis; each device owns one stage's stacked params;
a lax.scan over M + S - 1 ticks streams microbatches through a
collective-permute ring (the classic GPipe schedule, bubble fraction
(S-1)/(M+S-1)).

This is deliberately jax-native (shard_map + ppermute, no NCCL-style
emulation) per the brief's hardware-adaptation guidance.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          mesh: Mesh, axis: str = "stage"):
    """Build a pipelined apply: (stage_params_stacked [S, ...],
    microbatches [M, mb, ...]) -> outputs [M, mb, ...].

    ``stage_fn(params_one_stage, x) -> y`` must be shape-preserving
    (x and y share shape/dtype — standard residual-stack stages).
    """
    S = int(mesh.shape[axis])

    def body(params_local, xs):
        # params_local: [1, ...] (this device's stage); xs: [M, mb, ...]
        p = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        M = xs.shape[0]
        total = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (while available); other stages
            # consume what the previous stage permuted in
            feed = xs[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(idx == 0, feed, buf)
            y = stage_fn(p, inp)
            buf_next = jax.lax.ppermute(y, axis, perm)
            mb = t - (S - 1)
            take = jnp.clip(mb, 0, M - 1)
            upd = jnp.where((idx == S - 1) & (mb >= 0), y, outs[take])
            outs = outs.at[take].set(upd)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        # the carry becomes device-varying over the stage axis inside the
        # loop; mark the initial values accordingly (shard_map VMA typing)
        buf0 = jax.lax.pcast(buf0, (axis,), to="varying")
        outs0 = jax.lax.pcast(outs0, (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(total))
        # replicate the last stage's outputs to every stage
        mask = (idx == S - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
