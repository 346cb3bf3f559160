"""The comparison that decides ``correct`` in a training cell, and the
program's side of it.

The program's compiled step and its state, built once, run the first steps
from the seed through ``Trainer.run`` on the cell's own batches; the plain
reference (``reference.train.train_readings``) follows the same steps.
Three numbers are compared, each against its limit:

- ``loss_gap``: the largest gap between the two losses of a step, as a
  share of the reference's;
- ``grad_gap``: the norm of each leaf of the first gradient as the
  optimizer gets it (the program's worked out from Adam's first moment after
  one step), the worst leaf's gap;
- ``update_gap``: the norm of each leaf's change over the steps (the
  program's float32 master weights against the seed's weights), the worst
  leaf's gap, over leaves whose first reference gradient is at least a
  thousandth of the median leaf's (the rest move by round-off alone).

A leaf's gap is ``|program - reference|`` over the larger of the reference's
norm of that leaf and of the median leaf.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: List[str]) -> Dict[str, float]:
    med = float(np.median([ref[k] for k in ref]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}


def compare(prog: Dict, ref: Dict, *, by_leaf: bool = False) -> Dict:
    """The three numbers; with ``by_leaf``, also each leaf's gaps."""
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, g in ref["grad"].items() if g >= 1e-3 * med]
    grad = leaf_gaps(prog["grad"], ref["grad"], list(ref["grad"]))
    change = leaf_gaps(prog["change"], ref["change"], moved)
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(prog["loss"], ref["loss"])),
           "grad_gap": max(grad.values()),
           "update_gap": max(change.values())}
    if by_leaf:
        out.update(grad_by_leaf=grad, update_by_leaf=change)
    return out


def program_readings(arch_cfg, corpus, seed: int, opt: Dict,
                     steps: int = 3) -> Dict:
    """Run ``steps`` steps of the instrumented ``Trainer`` from the seed's
    state and read the loss of each, the first gradient and the change."""
    import jax
    import jax.numpy as jnp
    from repro.optim.adamw import AdamWConfig
    from repro.train import Trainer
    tr = Trainer(arch_cfg, seq_len=corpus.seq_len, batch=corpus.batch,
                 data=corpus, seed=seed, opt=AdamWConfig(**opt))

    @jax.jit
    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(tree)]

    # the program's own (eager) init: under one jit, XLA on a TPU keeps the
    # float32 master weights unrounded while it rounds the bf16 parameters,
    # so the change would be read from another start than the reference's
    state = tr.init_state()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(state.params)[0]]
    state = tr.run(1, state=state)
    grad = [float(n) / (1 - opt["b1"]) for n in norms(state.opt.mu)]
    state = tr.run(steps, state=state)
    losses = [h["loss"] for h in list(tr.metrics_history)[-steps:]]
    p0 = jax.jit(tr.model.init)(jax.random.PRNGKey(seed))
    change = jax.jit(lambda m, p: norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), m, p)))(state.opt.master, p0)
    del state, p0
    return {"loss": losses, "grad": dict(zip(paths, grad)),
            "change": dict(zip(paths, map(float, change)))}
