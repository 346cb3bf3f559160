"""Paper Fig. 4: hook-synchronization overhead vs parallelism.

Threads -> data-parallel shards: the WorkMeter's dynamic counters need a
cross-shard psum, so hook cost grows with the DP degree.  Each shard count
runs in a subprocess (XLA locks the host device count at first init).

A CPU-only rehearsal: the children fake host devices with ``XLA_FLAGS`` and
start after the parent has configured JAX, so never run it on a TPU, where
one process owns the chip."""
from __future__ import annotations

import json
import subprocess
import sys
from typing import List

from benchmarks.common import Row

_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
shard_map = jax.shard_map

n = %d
mesh = Mesh(np.array(jax.devices()).reshape(n), ("dp",))
D = 256
def work(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x

def step_plain(x):
    return shard_map(lambda v: work(v), mesh=mesh, in_specs=P("dp"),
                     out_specs=P("dp"))(x)

def step_hooked(x, counts):
    def f(v, c):
        v = work(v)
        c = c + jnp.ones((16,), jnp.int32)          # hook counters
        c = jax.lax.psum(c, "dp")                    # synchronization
        return v, c
    return shard_map(f, mesh=mesh, in_specs=(P("dp"), P()),
                     out_specs=(P("dp"), P()))(x, counts)

x = jnp.ones((n * 4, D, D)) * 0.01
c = jnp.zeros((16,), jnp.int32)
r = step_plain(x); jax.block_until_ready(r)
r, c2 = step_hooked(x, c); jax.block_until_ready(r)

def t(fn, reps=10):
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out[0] if isinstance(out, tuple) else out)
    return (time.perf_counter() - t0) / reps

tp = t(lambda: step_plain(x))
th = t(lambda: step_hooked(x, c))
print(json.dumps({"plain_us": tp * 1e6, "hooked_us": th * 1e6}))
"""


def run() -> List[Row]:
    rows: List[Row] = []
    for n in (1, 2, 4, 8):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD % (n, n)],
            capture_output=True, text=True, cwd=".")
        try:
            d = json.loads(out.stdout.strip().splitlines()[-1])
        except Exception:
            rows.append((f"sync_scaling/shards={n}", 0.0,
                         f"error:{out.stderr[-120:]}"))
            continue
        ratio = d["hooked_us"] / d["plain_us"]
        rows.append((f"sync_scaling/shards={n}", d["hooked_us"],
                     f"hook_sync_overhead={ratio:.3f}x"))
    return rows
