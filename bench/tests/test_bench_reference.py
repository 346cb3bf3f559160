"""The plain float32 references against the program at a size a CPU test
run holds: the same weights from a seed, the same loss, and the same
gradient once the program's cross-entropy is computed by logsumexp (its
own cross-entropy's gradient differs, see PERF.md); and a step computed in
a lower precision than the configuration's, or over half of the batch,
fails the training comparison."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import DENSE, SSM
from bench import common, train_check
from bench.corpus import Corpus
from bench.reference import dense, ssm, train
from repro.models import model_zoo

FAMILIES = [(dense, DENSE), (ssm, SSM)]
IDS = ["dense", "ssm"]
SEED = 2**31 + 5


def _program(conf):
    return common.load_module("program", conf["family"]).program_config(conf)


def logsumexp_ce(logits, labels, vocab_size, *, z_loss=1e-4):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    nll = lse - jnp.take_along_axis(lf, labels[..., None], -1)[..., 0]
    return jnp.mean(nll) + z_loss * jnp.mean(jnp.square(lse)), nll


def _batch():
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32),
            jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32))


@pytest.mark.parametrize("fam,conf", FAMILIES, ids=IDS)
def test_same_weights_from_a_seed(fam, conf):
    key = jax.random.PRNGKey(SEED)
    prog = model_zoo.build_model(_program(conf)).init(key)
    ref = train.init_params(fam.param_specs(conf), key)
    assert train.leaf_paths(fam.param_specs(conf)) == [
        "/".join(k.key for k in p)
        for p, _ in jax.tree_util.tree_flatten_with_path(prog)[0]]
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32), b)


@pytest.mark.parametrize("fam,conf", FAMILIES, ids=IDS)
def test_loss_and_gradient_match_the_program(fam, conf, monkeypatch):
    cfg = dataclasses.replace(_program(conf), param_dtype="float32",
                              compute_dtype="float32")
    p = train.init_params(fam.param_specs(conf), jax.random.PRNGKey(SEED))
    toks, lbl = _batch()
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(lambda q: fam.loss(
            q, toks, lbl, conf, train.matmul("f32")))(p)
        m = model_zoo.build_model(cfg)
        lp = m.loss(p, {"tokens": toks, "labels": lbl})[0]
        monkeypatch.setattr(model_zoo, "cross_entropy", logsumexp_ce)
        gp = jax.grad(lambda q: m.loss(
            q, {"tokens": toks, "labels": lbl})[0])(p)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(
            jnp.linalg.norm(b)) + 1e-7


@pytest.mark.parametrize("fam,conf", FAMILIES, ids=IDS)
def test_lower_precision_and_half_batch_fail(fam, conf, monkeypatch):
    """The program's bf16 step (cross-entropy by logsumexp) against the
    reference, beside the reference in fp8 and over half the batch: each
    reads the first gradient more than three times further off."""
    traffic = common.read_json(common.BENCH, "traffic", "pretrain_4k_b1.json")
    traffic.update(seq_len=64, batch=2)
    opt = traffic["optimizer"]
    corpus = Corpus(traffic, conf["vocab_size"], SEED, 3)
    batches = [corpus.batch_at(i) for i in range(3)]
    ref = train.train_readings(fam, conf, SEED, batches, opt)
    monkeypatch.setattr(model_zoo, "cross_entropy", logsumexp_ce)
    prog = train_check.compare(train_check.program_readings(
        _program(conf), corpus, SEED, opt), ref)
    ctrl = train_check.compare(train.train_readings(
        fam, conf, SEED, batches, opt, precision="fp8"), ref)
    half = train_check.compare(train.train_readings(
        fam, conf, SEED, batches, opt, half_batch=True), ref)
    assert ctrl["grad_gap"] > 3 * prog["grad_gap"], (ctrl, prog)
    assert half["grad_gap"] > 3 * prog["grad_gap"], (half, prog)
    assert half["update_gap"] > 3 * prog["update_gap"], (half, prog)
