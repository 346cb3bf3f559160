"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; call it only after the launcher has configured
``XLA_FLAGS`` (dryrun.py) or on real hardware.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    """Auto (GSPMD-propagated) axes: the sharding rules annotate with
    ``with_sharding_constraint`` and leave the rest to the partitioner."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1):
    """Small mesh over however many (host) devices exist — tests/benches."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
