"""Pipeline parallelism: GPipe over a 4-stage mesh equals sequential apply
(subprocess: needs >1 host device)."""
import json
import subprocess
import sys

import pytest

from repro.distributed.pipeline import bubble_fraction

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import gpipe

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
S, M, B, D = 4, 6, 2, 8
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (S, D, D)) * 0.3
b = jax.random.normal(jax.random.fold_in(key, 1), (S, D)) * 0.1
params = {"w": w, "b": b}
xs = jax.random.normal(jax.random.fold_in(key, 2), (M, B, D))

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

# sequential reference
ref = xs
for s in range(S):
    ref = jnp.stack([stage_fn({"w": w[s], "b": b[s]}, ref[m])
                     for m in range(M)])

piped = gpipe(stage_fn, mesh)(params, xs)
err = float(jnp.max(jnp.abs(piped - ref)))
print(json.dumps({"err": err}))
"""


def test_gpipe_matches_sequential():
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, cwd=".")
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["err"] < 1e-5, d


def test_bubble_fraction():
    assert bubble_fraction(4, 6) == pytest.approx(3 / 9)
    assert bubble_fraction(1, 8) == 0.0
