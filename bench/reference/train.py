"""Plain float32 training steps of a reference model, for the comparison
that decides ``correct`` in training cells.

Nothing here imports the program.  The family modules (``dense``, ``ssm``)
give a parameter layout and a loss; this module draws the weights from the
seed, runs AdamW for a few steps, and reports what the comparison reads: the
loss of each step, the norm of each leaf of the first gradient as the
optimizer gets it (after clipping), and the norm of each leaf's change over
all the steps.

The weights are drawn by the rule the program's configuration uses, so that
the same seed gives the same starting point: one key per leaf, split from
``PRNGKey(seed)`` in the sorted order of the leaf paths; ``normal`` leaves
are 0.02·N(0, 1), ``scaled`` leaves N(0, 1)/sqrt(first dimension), ``a_log``
leaves log U(1, 16); all rounded to bfloat16, the type the configuration
trains in, then held in float32.

Precision ``f32`` runs every matrix product in float32 at ``HIGHEST``.
Precision ``fp8`` is the control: every matrix product takes its operands
rounded to float8 e4m3 and its output gradient rounded to float8 e5m2, each
with a per-tensor scale (the usual fp8 training recipe); the rest stays in
float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str                   # normal | scaled | ones | zeros | a_log


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def leaf_paths(tree) -> List[str]:
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def init_params(specs, key):
    """float32 weights from ``PRNGKey(seed)`` (rounded through bfloat16)."""
    flat, treedef = jax.tree.flatten(specs, is_leaf=is_leaf)
    keys = jax.random.split(key, len(flat))

    def draw(leaf: Leaf, key):
        if leaf.init == "normal":
            w = 0.02 * jax.random.normal(key, leaf.shape)
        elif leaf.init == "scaled":
            w = (1.0 / math.sqrt(max(leaf.shape[0], 1))
                 * jax.random.normal(key, leaf.shape))
        elif leaf.init == "ones":
            w = jnp.ones(leaf.shape)
        elif leaf.init == "zeros":
            w = jnp.zeros(leaf.shape)
        elif leaf.init == "a_log":
            w = jnp.log(jax.random.uniform(key, leaf.shape, minval=1.0,
                                           maxval=16.0))
        else:
            raise ValueError(leaf.init)
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    return jax.tree.unflatten(treedef,
                              [draw(s, k) for s, k in zip(flat, keys)])


# -- matrix products --------------------------------------------------------
def _round(x, dtype):
    """x rounded to ``dtype`` under a per-tensor scale; gradient passes
    straight through."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    q = (x / s).astype(dtype).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _round_grad(y):
    return y


def _round_grad_fwd(y):
    return y, None


def _round_grad_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


_round_grad.defvjp(_round_grad_fwd, _round_grad_bwd)


def matmul(precision: str) -> Callable:
    """``mm(spec, a, b)``: an einsum of two float32 operands."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        def mm(spec, a, b):
            e4 = jnp.float8_e4m3fn
            return _round_grad(jnp.einsum(spec, _round(a, e4), _round(b, e4),
                                          precision=HIGHEST))
        return mm
    raise ValueError(precision)


# -- pieces shared by the families -----------------------------------------
def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def lm_loss(x, emb, labels, mm, *, z_loss: float, rows: int = 512):
    """Mean next-token cross-entropy plus ``z_loss``·mean(lse²) over the tied
    head ``x @ embᵀ``, ``rows`` tokens at a time."""
    d = x.shape[-1]
    x = x.reshape(-1, d)
    labels = labels.reshape(-1)
    n = x.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} tokens do not split into blocks of {rows}")

    @jax.checkpoint
    def block(xb, lb):
        logits = mm("td,vd->tv", xb, emb)
        lse = jax.nn.logsumexp(logits, axis=-1)
        own = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - own), jnp.sum(lse * lse)

    def body(acc, xs):
        nll, zz = block(*xs)
        return (acc[0] + nll, acc[1] + zz), None

    (nll, zz), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())),
        (x.reshape(n // rows, rows, d), labels.reshape(n // rows, rows)))
    return nll / n + z_loss * zz / n


# -- training ----------------------------------------------------------------
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def make_step(loss_fn: Callable, opt: Dict):
    """One AdamW step (global-norm clipping, decoupled weight decay on every
    leaf, bias correction), with the per-leaf norms of the clipped
    gradient."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr, clip = opt["lr"], opt["grad_clip"]

    def step(params, mu, nu, t, tokens, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        tf = t.astype(jnp.float32)
        b1c, b2c = 1 - b1 ** tf, 1 - b2 ** tf
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu, loss, _leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(family, conf: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]], opt: Dict,
                   *, precision: str = "f32", half_batch: bool = False
                   ) -> Dict:
    """Run ``len(batches)`` reference steps from the seed's weights.

    ``half_batch`` plants a fault: the loss is the mean over half of the
    batch only (the first half of the rows, or of the positions when there
    is one row).
    """
    specs = family.param_specs(conf)
    paths = leaf_paths(specs)
    mm = matmul(precision)

    def loss_fn(params, tokens, labels):
        if half_batch:
            b, s = tokens.shape
            tokens, labels = ((tokens[:b // 2], labels[:b // 2]) if b > 1
                              else (tokens[:, :s // 2], labels[:, :s // 2]))
        return family.loss(params, tokens, labels, conf, mm)

    key = jax.random.PRNGKey(seed)
    init = jax.jit(lambda k: init_params(specs, k))
    with jax.default_matmul_precision("highest"):
        step = make_step(loss_fn, opt)
        params = init(key)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, grad = [], None
        for t, b in enumerate(batches, start=1):
            params, mu, nu, loss, gn = step(
                params, mu, nu, jnp.int32(t), jnp.asarray(b["tokens"]),
                jnp.asarray(b["labels"]))
            losses.append(loss)
            if grad is None:
                grad = gn
        del mu, nu
        p0 = init(key)
        change = jax.jit(lambda a, b: _leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(params, p0)
        del params, p0
    return {"loss": [float(x) for x in losses],
            "grad": dict(zip(paths, map(float, grad))),
            "change": dict(zip(paths, map(float, change)))}
