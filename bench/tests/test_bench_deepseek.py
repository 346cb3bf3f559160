"""The DeepSeek-V3 configuration (``moonlight-16b-a3b.pp2ep8``) at a size a
CPU run holds: the reference's layout is the program's, served logits match
the reference's full forward pass, the shares of an expert-parallel group
add up to the uncut layer, and the ``serve_spans`` window kind records the
engine's spans and the router's counts."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import ROOT
from bench import common
from bench.kinds import serve, serve_spans
from bench.reference import deepseek_v3 as ref
from bench.reference import train
from repro import obs
from repro.models import moe as M
from repro.models.model_zoo import build_model
from repro.serve.engine import Request, ServeEngine

SEED = 2**31 + 29
CELL = "moonlight-16b-a3b.pp2ep8.serve"
# Moonlight's block at a tiny size: a router over 16 experts, 2 held here
# (8 shares), 6 chosen a token, one dense layer then two expert layers
TINY = dict(
    name="tiny-deepseek", family="deepseek_v3", source="test",
    num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    n_routed_experts=2, n_shared_experts=2, num_experts_per_tok=6,
    n_group=1, topk_group=1, norm_topk_prob=True, topk_method="noaux_tc",
    scoring_func="sigmoid", moe_layer_freq=1, routed_scaling_factor=2.446,
    rope_theta=50000, rms_norm_eps=1e-5, tie_word_embeddings=False,
    hidden_act="silu",
    published={"num_hidden_layers": 3, "n_routed_experts": 16},
    expert_parallel={"size": 8, "rank": 0})


def tiny_cell(limit: float = 0.01) -> common.Cell:
    """The cell's kind, traffic and layout at the tiny size.  Over eight
    seeds the program's widest logit gap here read 0 to 0.023 and the fp8
    control's 0.020 to 0.79, so at this size no one limit parts every seed
    (a bf16 near-tie moved one served token); at the tests' seed they read
    0 and 0.060, and the limit lies between."""
    bm = common.read_json(ROOT, "BENCHMARK.json")
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    traffic = copy.deepcopy(common.read_json(
        common.BENCH, "traffic", f"{entry['traffic']}.json"))
    traffic.update(prompt_len=16, rate_per_s=10.0,
                   output_len={"median": 4, "sigma": 0.5, "min": 2,
                               "max": 8})
    spec = copy.deepcopy(common.read_json(common.BENCH, "workloads",
                                          f"{CELL}.json"))
    spec.update(slots=2, max_seq=64, sample_requests=3, trace_seconds=1,
                limits={"logit_gap": limit})
    return common.Cell("tiny.moonlight", 1, spec, dict(TINY), traffic, [],
                       [])


def _program(conf, **kw):
    from bench.program import deepseek_v3
    return dataclasses.replace(deepseek_v3.program_config(conf), **kw)


def test_configuration_as_published():
    """The committed configuration: every published width, the router's
    64 outputs and 6 choices, 8 experts held, those of rank 0."""
    conf = common.read_json(common.BENCH, "configs",
                            "moonlight-16b-a3b.pp2ep8.json")
    cfg = _program(conf)
    assert (cfg.family, cfg.n_layers, cfg.first_k_dense) == ("mla_moe", 14, 1)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (2048, 11264, 163840)
    a = cfg.attn
    assert (a.n_heads, a.head_dim, a.mla.rope_dim, a.mla.v_head_dim,
            a.mla.kv_lora_rank) == (16, 128, 64, 128, 512)
    m = cfg.moe
    assert (m.n_experts, m.top_k, m.n_local, m.held_first) == (64, 6, 8, 0)
    assert (m.d_expert, m.d_shared, m.routed_scale) == (1408, 2816, 2.446)
    assert set(conf["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    with pytest.raises(ValueError):
        _program(dict(conf, q_lora_rank=1536))


def test_reference_layout_is_the_programs():
    """One seed gives one set of weights: the same leaves, in the same
    order, of the same shapes, drawn alike."""
    specs = ref.param_specs(TINY)
    model = build_model(_program(TINY))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert train.leaf_paths(specs) == train.leaf_paths(shapes)
    assert ([s.shape for s in jax.tree.leaves(specs, is_leaf=train.is_leaf)]
            == [s.shape for s in jax.tree.leaves(shapes)])
    key = jax.random.PRNGKey(SEED % 2**31)
    want = train.init_params(specs, key)
    got = model.init(key)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b, np.float32))


def test_served_logits_match_the_reference():
    """Prefill, then decode through the cache, in ``ServeEngine`` (float32
    program): every step's logits match the reference's full forward pass
    over the served sequence.  Tolerance 1e-4 of the largest logit: both
    sides are float32, summed in different orders (absorbed against
    decompressed attention, dispatch against every expert)."""
    cfg = _program(TINY, param_dtype="float32", compute_dtype="float32")
    eng = ServeEngine(cfg, batch=2, max_seq=40, prefill_len=16)
    seen = []

    def keep(fn):
        def call(*a):
            out = fn(*a)
            seen.append(np.asarray(out[0])[:, -1])
            return out
        return call
    eng._prefill, eng._decode = keep(eng._prefill), keep(eng._decode)
    # the seed's weights as the reference draws them (through bfloat16)
    params = train.init_params(ref.param_specs(TINY), jax.random.PRNGKey(7))
    prompt = np.random.default_rng(1).integers(0, 256, 16)
    eng.run(params, [Request(0, prompt, 10)])
    out = eng.done[0].output
    toks = jnp.asarray(np.concatenate([prompt, out[:-1]])[None])
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, toks, TINY, train.matmul("f32"), 15)[0]
    got = np.stack([s[0] for s in seen])           # slot 0 of every step
    assert got.shape == want.shape == (11, 256)
    err = np.max(np.abs(got - np.asarray(want)))
    assert err < 1e-4 * np.max(np.abs(np.asarray(want))), err


def test_shares_add_up_to_the_uncut_layer():
    """The eight ranks' held experts, with the shared experts counted once,
    give what the reference's uncut expert layer (all 16 experts held)
    gives."""
    uncut = dict(TINY, n_routed_experts=16)
    key = jax.random.PRNGKey(11)
    with jax.default_matmul_precision("highest"):
        full = train.init_params(ref.param_specs(uncut), key)
    p = jax.tree.map(lambda a: a[0], full["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, x, uncut, train.matmul("f32"))
        shared = ref.swiglu(p["shared"], x, train.matmul("f32"))
    total = shared
    for rank in range(8):
        conf = dict(TINY, expert_parallel={"size": 8, "rank": rank})
        cfg = _program(conf, param_dtype="float32", compute_dtype="float32")
        share = dict(p, **{k: p[k][2 * rank:2 * rank + 2]
                           for k in ("wi", "wg", "wo")})
        with jax.default_matmul_precision("highest"):
            y, aux = M.moe_held(share, cfg, x)
        assert int(aux["dropped_tokens"]) == 0
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(
                                   jnp.abs(want))))


@pytest.fixture(scope="module")
def traced_run():
    return serve_spans.run(tiny_cell(), SEED, 2.0, True)


def test_traced_run_keeps_the_spans(traced_run):
    """A ``--trace 1`` run is correct and leaves the engine's spans of the
    untraced part of the window in ``record["spans"]`` as ``(name, start
    s, seconds, attrs)``; the two readers find numbers in them; the notes
    carry the router's counts, with nothing dropped."""
    out = traced_run
    assert common.correct(out["checks"]), out["checks"]
    assert out["failed"] == 0 and not obs.enabled()
    spans = out["record"]["spans"]
    assert spans and all(
        isinstance(n, str) and isinstance(a, dict) and 0 <= s and 0 <= d
        and s + d <= 1.0 + 1e-6 for n, s, d, a in spans)
    assert {"serve.step", "serve.decode", "serve.read_tokens",
            "serve.prefill", "serve.read_first"} <= {n for n, *_ in spans}
    for metric in ("decode_read_ms", "insert_read_ms"):
        v = common.load_module("metrics", metric).read(out["record"])
        assert v is not None and v > 0, metric
    decodes = [a for n, _, _, a in spans if n == "serve.decode"]
    assert all(a["held_tokens"] >= a["expert_load_max"] >= 0
               for a in decodes)
    notes = out["notes"]
    assert notes["dropped_tokens"] == 0
    assert notes["held_tokens_per_decode"] > 0
    assert notes["held_tokens_per_prefill"] > notes["held_tokens_per_decode"]
    assert notes["expert_load_max_over_mean_prefill"] >= 1


def test_untraced_run_is_serve(monkeypatch):
    """A ``--trace 0`` run leaves ``repro.obs`` off and keeps no spans,
    and still notes the router's counts."""
    seen = []
    real = ServeEngine.step

    def step(self, params):
        seen.append(obs.enabled())
        return real(self, params)
    monkeypatch.setattr(ServeEngine, "step", step)
    out = serve_spans.run(tiny_cell(), SEED, 1.0, False)
    assert seen and not any(seen) and not obs.enabled()
    assert "spans" not in out["record"]
    assert out["notes"]["dropped_tokens"] == 0
    assert serve.serve.__name__ == "serve"


def test_control_reads_above_the_program():
    """The reference computed in fp8, put in the program's place, reads
    more than three times the program's widest gap, and the run's own
    comparison at the tiny cell's limit passes the program and fails it."""
    from bench import arrivals
    cell = tiny_cell()
    eng, init = serve.build(cell)
    params = init(jax.random.PRNGKey(SEED))
    sched = arrivals.schedule(cell.traffic, 2.0)
    served = serve.Served(sched, arrivals.prompts(
        cell.traffic, cell.config["vocab_size"], SEED, len(sched)))
    serve.serve(eng, params, served, 2.0)
    ids = serve.sample(served, SEED, cell.spec["sample_requests"])
    prog = serve.reference_gaps(cell, SEED, served, ids)
    ctrl = serve.reference_gaps(cell, SEED, served, ids, control=True)
    assert ctrl.max() > 3 * prog.max(), (ctrl.max(), prog.max())
    assert common.correct(serve.checks(cell, prog))
    assert not common.correct(serve.checks(cell, ctrl)), ctrl.max()
