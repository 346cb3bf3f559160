"""Train state + step construction (pure functions; the Trainer wires I/O)."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, dtype_of
from repro.core.meter import init_meter, tick_step
from repro.core.registry import BlockTable
from repro.models.model_zoo import Model
from repro.optim.adamw import (AdamWConfig, OptState, adamw_update,
                               init_opt_state)


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt: OptState
    rng: jax.Array
    meter: Optional[Dict[str, jax.Array]]


def init_train_state(model: Model, key: jax.Array, opt_cfg: AdamWConfig,
                     table: Optional[BlockTable] = None) -> TrainState:
    params = model.init(key)
    opt = init_opt_state(params, opt_cfg)
    meter = init_meter(table) if table is not None else None
    state = TrainState(jnp.zeros((), jnp.int32), params, opt,
                       jax.random.fold_in(key, 1), meter)
    # JAX caches equal constants: distinct zero leaves can alias the same
    # buffer, which breaks donate_argnums ("donate the same buffer twice").
    # Copy each leaf so every leaf owns its buffer, one leaf at a time so
    # the device never holds two copies of the whole state.
    leaves, treedef = jax.tree.flatten(state)
    del state, params, opt, meter
    for i, x in enumerate(leaves):
        leaves[i] = x.copy() if hasattr(x, "copy") else x
    return jax.tree.unflatten(treedef, leaves)


def make_train_step(model: Model, opt_cfg: AdamWConfig, lr_fn: Callable,
                    *, table: Optional[BlockTable] = None,
                    microbatch: int = 1,
                    instrument: bool = True) -> Callable:
    """Build the jit-able train step: (state, batch) -> (state, metrics, aux).

    ``microbatch`` > 1 splits the global batch into that many accumulation
    slices (lax.scan, f32 accumulators) — the activation-memory lever for the
    123B-arch cells.  When ``instrument`` and a BlockTable is given the
    WorkMeter hook (paper §III-C1) runs inside the step.
    """
    def loss_fn(params, batch, rng):
        return model.loss(params, batch, rng=rng)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        rng = jax.random.fold_in(state.rng, state.step)
        if microbatch > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatch, b // microbatch, *x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_body(carry, mslice):
                gacc, lacc, aux_acc = carry
                (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mslice, rng)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / microbatch,
                    gacc, g)
                aux_acc = {k: aux_acc[k] + aux[k] for k in aux_acc}
                return (gacc, lacc + l / microbatch, aux_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state.params)
            m0 = jax.tree.map(lambda x: x[0], mb)
            aux0 = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(lambda: loss_fn(state.params, m0, rng)[1]))
            (grads, loss, aux), _ = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32), aux0), mb)
        else:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, rng)

        lr = lr_fn(state.step)
        new_params, new_opt, om = adamw_update(state.params, grads,
                                               state.opt, opt_cfg, lr)
        meter = state.meter
        if instrument and table is not None and meter is not None:
            meter = tick_step(meter, table, aux)
        metrics = {"loss": loss, **om}
        new_state = TrainState(state.step + 1, new_params, new_opt,
                               state.rng, meter)
        return new_state, metrics, aux

    return train_step
