"""Kimi Delta Attention and the ``kda_mla_moe`` family at a tiny size: the
chunked prefill against the token-by-token recurrence (weak and strong
decay, chunk boundaries inside the sequence, a carried state), the layer
plan that puts each parameter stack in one scan, the scopes the profiler
reads, and prefill then decode through the engine's cache against the
full forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.hlo_analysis import find_scope_labels
from repro.models import kda as K
from repro.models import transformer as T
from repro.models.model_zoo import build_model
from repro.serve.engine import Request, ServeEngine


def _inputs(seed, decay, b=2, s=100, h=3, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = K.l2norm(jax.random.normal(ks[0], (b, s, h, d))) / 4
    k = K.l2norm(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = -decay * jax.random.exponential(ks[3], (b, s, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, d, d))
    return q, k, v, g, beta, state


def _recurrence(q, k, v, g, beta, state):
    """The decode's one-position step, position after position."""
    def step(st, xs):
        o, st = K.kda_step(*xs, st)
        return st, o
    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# mean log decay a position: weak (the state lasts across chunks), the
# drawn weights' order, and strong enough that a chunk's cumulative decay
# passes e^-2000, far beyond float32
@pytest.mark.parametrize("decay", [0.01, 0.5, 20.0])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_is_the_recurrence(decay, chunk):
    """Chunks of 16 or 32 over 100 positions (boundaries inside, a padded
    last chunk), from a non-zero state: every output and the final state
    match the recurrence to float32 rounding."""
    q, k, v, g, beta, state = _inputs(0, decay)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = K.chunk_kda(q, k, v, g, beta, chunk, state)
    assert float(jnp.min(jnp.cumsum(g, 1))) < -decay * 50
    scale = float(jnp.max(jnp.abs(want_o)))
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5 * float(jnp.max(jnp.abs(want_s))),
                               rtol=0)


def test_chunk_must_hold_whole_sub_blocks():
    q, k, v, g, beta, _ = _inputs(1, 0.1, s=20)
    with pytest.raises(ValueError):
        K.chunk_kda(q, k, v, g, beta, 24)


def test_decode_step_is_the_delta_rule():
    """One step against the update written out:
    S' = (I - b k k^T) Diag(a) S + b k v^T, o = S'^T q."""
    q, k, v, g, beta, state = _inputs(2, 0.3, s=1)
    o, new = K.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state)
    kk, bb = k[:, 0], beta[:, 0][..., None, None]
    proj = jnp.eye(16) - bb * kk[..., :, None] * kk[..., None, :]
    want = (jnp.einsum("bhij,bhjv->bhiv", proj,
                       jnp.exp(g[:, 0])[..., None] * state,
                       precision="highest")
            + bb * kk[..., :, None] * v[:, 0][..., None, :])
    np.testing.assert_allclose(np.asarray(new), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(jnp.einsum("bhkv,bhk->bhv", want,
                                             q[:, 0], precision="highest")),
        rtol=1e-5, atol=1e-5)


def _kimi(n_layers):
    return dataclasses.replace(get_config("kimi-linear-48b-a3b"),
                               n_layers=n_layers)


def test_layer_plan_reads_each_stack_in_one_scan():
    """The published schedule cut to 14 layers: the dense layer, three
    periods of (KDA, KDA, MLA, KDA) in one scan, the last KDA layer; every
    stack belongs to one scan and its rows are that scan's iterations."""
    plan = T.layer_plan(_kimi(14))
    assert [(reps, [st for st, *_ in layers]) for reps, layers in plan] == [
        (1, ["layer00_kda_mlp"]),
        (3, ["layer01_kda_moe", "layer02_kda_moe", "layer03_mla_moe",
             "layer04_kda_moe"]),
        (1, ["layer13_kda_moe"])]
    # index among each kind, iteration by iteration, covers every layer once
    seen = {"kda": [], "mla": []}
    for reps, layers in plan:
        for it in range(reps):
            for _, kind, j0, dj in layers:
                seen[kind].append(j0 + it * dj)
    assert sorted(seen["kda"]) == list(range(11))
    assert sorted(seen["mla"]) == [0, 1, 2]
    specs = build_model(_kimi(14)).specs()
    assert {k: jax.tree.leaves(v, is_leaf=lambda s: hasattr(s, "axes"))[0]
            .shape[0] for k, v in specs.items() if k.startswith("layer")} \
        == {"layer00_kda_mlp": 1, "layer01_kda_moe": 3, "layer02_kda_moe": 3,
            "layer03_mla_moe": 3, "layer04_kda_moe": 3, "layer13_kda_moe": 1}


def test_cache_holds_both_kinds_of_state():
    m = build_model(_kimi(14))
    cache = jax.eval_shape(lambda: m.init_cache(4, 256))
    assert cache["kda_state"].shape == (11, 4, 32, 128, 128)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (11, 4, 3, 3 * 32 * 128)
    assert cache["c_kv"].shape == (3, 4, 256, 512)
    assert cache["k_pe"].shape == (3, 4, 256, 64)


def test_scopes_mark_both_kinds_in_both_programs():
    """``nugget_block_kda`` marks the KDA layers' operations in the prefill
    and in the decode, ``nugget_block_mla`` the latent layers'."""
    cfg = reduced(get_config("kimi-linear-48b-a3b"), n_layers=4)
    m = build_model(cfg)
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: m.init_cache(2, 32))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    prefill = jax.jit(lambda p, t, c: m.prefill(p, {"tokens": t}, c)).lower(
        params, toks, cache).compile().as_text()
    decode = jax.jit(m.decode_step).lower(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32),
        cache).compile().as_text()
    for hlo in (prefill, decode):
        assert find_scope_labels(hlo, "nugget_block_kda")
        assert find_scope_labels(hlo, "nugget_block_mla")


def test_engine_prefill_then_decode_is_the_forward_pass():
    """Through ``ServeEngine`` (float32, a prompt longer than a chunk):
    the first token's logits and every decode's match the model's full
    forward pass over the served sequence."""
    cfg = reduced(get_config("kimi-linear-48b-a3b"), n_layers=4)
    eng = ServeEngine(cfg, batch=2, max_seq=96, prefill_len=72)
    assert eng.table.step_uow("decode") > 0
    seen = []

    def keep(fn):
        def call(*a):
            out = fn(*a)
            seen.append(np.asarray(out[0])[:, -1])
            return out
        return call
    eng._prefill, eng._decode = keep(eng._prefill), keep(eng._decode)
    params = eng.model.init(jax.random.PRNGKey(3))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 72)
    eng.run(params, [Request(0, prompt, 6)])
    out = eng.done[0].output
    toks = jnp.asarray(np.concatenate([prompt, out[:-1]])[None])
    full, _ = jax.jit(eng.model.forward)(params, {"tokens": toks})
    got = np.stack([s[0] for s in seen])
    want = np.asarray(full[0, 71:])
    assert got.shape == want.shape == (7, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max(),
                               rtol=0)
