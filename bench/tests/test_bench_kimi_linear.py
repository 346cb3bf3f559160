"""The Kimi Linear configuration (``kimi-linear-48b-a3b.pp2ep32``) at a size a
CPU run holds: the committed file is the published configuration cut as it
says, the reference's layout is the program's, served logits match the
reference's full forward pass, the 32 shares of an expert-parallel group add
up to the uncut layer, the ``serve_kda`` window kind runs the cell's
comparison, and the KDA roofline's counts and trace reading."""
import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import ROOT
from bench import common, kda_roofline
from bench.kinds import serve, serve_kda
from bench.reference import kimi_linear as ref
from bench.reference import train
from repro import obs
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.model_zoo import build_model
from repro.serve.engine import Request, ServeEngine

SEED = 2**31 + 31
CELL = "kimi-linear-48b-a3b.pp2ep32.serve"
CONFIG = "kimi-linear-48b-a3b.pp2ep32"
# Kimi Linear's block at a tiny size: KDA and latent attention alternating
# (so the period (MLA, KDA) repeats), a router over 64 experts with 2 held
# here (32 shares), 8 chosen a token, one dense layer
TINY = dict(
    name="tiny-kimi", family="kimi_linear", source="test",
    num_hidden_layers=6, first_k_dense_replace=1, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    linear_attn_config={"full_attn_layers": [2, 4, 6],
                        "kda_layers": [1, 3, 5], "head_dim": 16,
                        "num_heads": 2, "short_conv_kernel_size": 4},
    mla_use_nope=True, num_experts=2, num_shared_experts=1,
    num_experts_per_token=8, num_expert_group=1, topk_group=1,
    moe_renormalize=True, moe_router_activation_func="sigmoid",
    moe_layer_freq=1, num_nextn_predict_layers=0,
    routed_scaling_factor=2.446, rope_theta=10000, rms_norm_eps=1e-5,
    tie_word_embeddings=False, hidden_act="silu",
    published={"num_hidden_layers": 6, "num_experts": 64},
    expert_parallel={"size": 32, "rank": 0})


def _program(conf, **kw):
    from bench.program import kimi_linear
    return dataclasses.replace(kimi_linear.program_config(conf), **kw)


def tiny_cell(limit: float = 0.05) -> common.Cell:
    """The cell's kind, traffic and layout at the tiny size, the prompt
    longer than the program's chunk."""
    bm = common.read_json(ROOT, "BENCHMARK.json")
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    traffic = copy.deepcopy(common.read_json(
        common.BENCH, "traffic", f"{entry['traffic']}.json"))
    traffic.update(prompt_len=80, rate_per_s=10.0,
                   output_len={"median": 4, "sigma": 0.5, "min": 2,
                               "max": 8})
    spec = copy.deepcopy(common.read_json(common.BENCH, "workloads",
                                          f"{CELL}.json"))
    spec.update(slots=2, max_seq=128, sample_requests=3, trace_seconds=1,
                limits={"logit_gap": limit, "logit_gap_mean": limit / 10})
    return common.Cell("tiny.kimi", 1, spec, dict(TINY), traffic, [], [])


def test_configuration_as_published():
    """The committed configuration: every published width, the schedule
    of KDA and latent layers, the router's 256 outputs and 8 choices, 8
    experts held, those of rank 0; the two cut keys beside their published
    values."""
    conf = common.read_json(common.BENCH, "configs", f"{CONFIG}.json")
    cfg = _program(conf)
    assert (cfg.family, cfg.n_layers, cfg.first_k_dense) == (
        "kda_mla_moe", 14, 1)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (2304, 9216, 163840)
    assert cfg.layer_kinds() == ("kda",) * 3 + ("mla",) + ("kda",) * 3 + (
        "mla",) + ("kda",) * 3 + ("mla",) + ("kda",) * 2
    k = cfg.kda
    assert (k.n_heads, k.head_dim, k.d_conv) == (32, 128, 4)
    a = cfg.attn
    assert (a.n_heads, a.head_dim, a.mla.rope_dim, a.mla.v_head_dim,
            a.mla.kv_lora_rank, a.mla.rope) == (32, 128, 64, 128, 512, False)
    m = cfg.moe
    assert (m.n_experts, m.top_k, m.n_local, m.held_first) == (256, 8, 8, 0)
    assert (m.d_expert, m.d_shared, m.routed_scale) == (1024, 1024, 2.446)
    assert set(conf["reduced"]) == {"num_hidden_layers", "num_experts"}
    assert conf["published"] == {"num_hidden_layers": 27, "num_experts": 256}
    assert conf["linear_attn_config"]["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    bm = common.read_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(conf["reduced"])
    with pytest.raises(ValueError):
        _program(dict(conf, mla_use_nope=False))


@pytest.mark.parametrize("conf", ["tiny", "committed"])
def test_reference_layout_is_the_programs(conf):
    """One seed gives one set of weights: the same leaves, in the same
    order, of the same shapes, drawn alike (drawn at the tiny size)."""
    c = TINY if conf == "tiny" else common.read_json(
        common.BENCH, "configs", f"{CONFIG}.json")
    specs = ref.param_specs(c)
    model = build_model(_program(c))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert train.leaf_paths(specs) == train.leaf_paths(shapes)
    assert ([s.shape for s in jax.tree.leaves(specs, is_leaf=train.is_leaf)]
            == [s.shape for s in jax.tree.leaves(shapes)])
    assert [stack for stack, _ in ref.layers(c)] == [
        stack for reps, layers in T.layer_plan(model.cfg)
        for _ in range(reps) for stack, *_ in layers]
    if conf == "tiny":
        key = jax.random.PRNGKey(SEED % 2**31)
        for a, b in zip(jax.tree.leaves(train.init_params(specs, key)),
                        jax.tree.leaves(model.init(key))):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b, np.float32))


def test_served_logits_match_the_reference():
    """Prefill (an 80-token prompt: a chunk boundary inside it), then
    decode through the cache, in ``ServeEngine`` (float32 program): every
    step's logits match the reference's full forward pass, its KDA run
    token by token.  Tolerance 1e-4 of the largest logit: both sides are float32, summed in
    different orders (chunked against step by step, absorbed against
    decompressed attention)."""
    cfg = _program(TINY, param_dtype="float32", compute_dtype="float32")
    eng = ServeEngine(cfg, batch=2, max_seq=96, prefill_len=80)
    seen = []

    def keep(fn):
        def call(*a):
            out = fn(*a)
            seen.append(np.asarray(out[0])[:, -1])
            return out
        return call
    eng._prefill, eng._decode = keep(eng._prefill), keep(eng._decode)
    params = train.init_params(ref.param_specs(TINY), jax.random.PRNGKey(7))
    prompt = np.random.default_rng(1).integers(0, 256, 80)
    eng.run(params, [Request(0, prompt, 10)])
    out = eng.done[0].output
    toks = jnp.asarray(np.concatenate([prompt, out[:-1]])[None])
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, toks, TINY, train.matmul("f32"), 79)[0]
    got = np.stack([s[0] for s in seen])           # slot 0 of every step
    assert got.shape == want.shape == (11, 256)
    err = np.max(np.abs(got - np.asarray(want)))
    assert err < 1e-4 * np.max(np.abs(np.asarray(want))), err


def test_shares_add_up_to_the_uncut_layer():
    """The 32 ranks' held experts, with the shared expert counted once,
    give what the reference's uncut expert layer (all 64 experts held)
    gives."""
    uncut = dict(TINY, num_experts=64)
    key = jax.random.PRNGKey(11)
    with jax.default_matmul_precision("highest"):
        full = train.init_params(ref.param_specs(uncut), key)
    p = jax.tree.map(lambda a: a[0], full["layer02_kda_moe"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 24, 64))
    mm = train.matmul("f32")
    with jax.default_matmul_precision("highest"):
        want = ref.ds.experts(p, x, ref._moe_conf(uncut), mm)
        shared = ref.ds.swiglu(p["shared"], x, mm)
    total = shared
    for rank in range(32):
        conf = dict(TINY, expert_parallel={"size": 32, "rank": rank})
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
    return serve_kda.run(tiny_cell(), SEED, 2.0, True)


def test_traced_run_is_correct_and_keeps_spans(traced_run):
    """A ``--trace 1`` run is correct, keeps the engine's spans for the
    readers that the cell shares with Moonlight's, and notes the router's
    counts with nothing dropped.  On the CPU the trace holds no TPU
    operations, so the KDA metrics read nothing, and say so by None."""
    out = traced_run
    assert common.correct(out["checks"]), out["checks"]
    assert set(out["checks"]) == {"logit_gap", "logit_gap_mean"}
    assert out["failed"] == 0 and not obs.enabled()
    for metric in ("decode_read_ms", "insert_read_ms"):
        v = common.load_module("metrics", metric).read(out["record"])
        assert v is not None and v > 0, metric
    assert out["notes"]["dropped_tokens"] == 0
    assert out["notes"]["held_tokens_per_prefill"] > 0
    assert 0 < out["notes"]["live_slots_mean"] <= \
        out["notes"]["live_slots_max"] <= 2
    for metric in ("kda_decode_roofline_pct", "kda_prefill_roofline_pct"):
        assert common.load_module("metrics", metric).read(
            out["record"]) is None
    assert serve.build.__name__ == "build"


def test_untraced_run_is_serve_spans():
    out = serve_kda.run(tiny_cell(), SEED, 1.0, False)
    assert "kda" not in out["record"] and "spans" not in out["record"]
    assert out["notes"]["dropped_tokens"] == 0
    assert out["notes"]["decodes"] > 0
    assert set(out["checks"]) == {"logit_gap", "logit_gap_mean"}
    assert serve.checks is not serve_kda.checks       # restored after the run
    assert serve.build.__name__ == "build"


def test_control_reads_above_the_program():
    """The reference computed in fp8, put in the program's place, reads
    more than three times the program's widest and mean gaps, and the
    run's own comparison at the tiny cell's limits passes the program and
    fails the control, by the mean gap alone too."""
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
    assert ctrl.mean() > 3 * prog.mean(), (ctrl.mean(), prog.mean())
    assert common.correct(serve_kda.checks(cell, prog))
    assert not common.correct(serve_kda.checks(cell, ctrl)), ctrl.max()
    # the mean alone fails the control
    cell.spec["limits"]["logit_gap"] = float("inf")
    assert not common.correct(serve_kda.checks(cell, ctrl)), ctrl.mean()


# -- the roofline's counts and the trace reading ----------------------------
def test_counts_at_the_cell():
    """At the cell's 128 slots a decode step's KDA bytes are the 11 layers'
    float32 states read and written (5.9 GB), their convolution states and
    their weights; the prefill's operations are at least the projections'
    2·tokens·weights and its bound is the operations."""
    conf = common.read_json(common.BENCH, "configs", f"{CONFIG}.json")
    state = 11 * 128 * 32 * 128 * 128 * 4
    weights = kda_roofline.weight_count(conf)
    cfg = _program(conf)
    specs = build_model(cfg).specs()["layer01_kda_moe"]["kda"]
    assert weights == sum(int(np.prod(s.shape[1:])) for s in jax.tree.leaves(
        specs, is_leaf=lambda s: hasattr(s, "axes")))
    assert kda_roofline.decode_bytes(conf, 128) == pytest.approx(
        2 * state + 2 * 11 * 128 * 3 * 3 * 4096 * 2 + 2 * 11 * weights)
    assert 2 * state / kda_roofline.decode_bytes(conf, 128) > 0.8
    flops = kda_roofline.prefill_flops(conf, 2048)
    assert flops > 2 * 2048 * 11 * weights * 0.99
    t = kda_roofline.roofline_s(flops, kda_roofline.prefill_bytes(conf, 2048),
                                "TPU v5 lite")
    assert t == pytest.approx(flops / 197e12)
    with pytest.raises(KeyError):
        kda_roofline.roofline_s(flops, 1.0, "cpu")


def test_prefill_count_is_under_the_programs():
    """The counted operations of a prefill's KDA layer are no more than
    the compiled layer's own (so the share stays under 100% unless time is
    left out)."""
    conf = dict(TINY, num_hidden_layers=1,
                linear_attn_config=dict(TINY["linear_attn_config"],
                                        num_heads=4, head_dim=32))
    cfg = _program(conf, param_dtype="float32",
                   compute_dtype="float32")
    sp = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0)))["layer00_kda_mlp"]
    x = jax.ShapeDtypeStruct((1, 256, 64), jnp.float32)
    compiled = jax.jit(lambda p, xx: T._kda_block(p, 0, cfg, xx)[0]).lower(
        sp, x).compile()
    assert kda_roofline.prefill_flops(conf, 256) <= \
        compiled.cost_analysis()["flops"]


def _xspace(path, ops, modules, window=(0, 10_000), host=(), stats=None):
    """A profiler trace file with one TPU plane: ``modules`` and ``ops``
    as (name, start ns, duration ns), each op with stats ``stats[i]`` (a
    dict), and on the host the window span and the spans ``host``."""
    from jax.profiler import ProfileData
    host = [("bench.traced",) + tuple(window)] + list(host)
    names = sorted({n for n, *_ in ops + modules + host})
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    keys = sorted({k for st in (stats or []) for k in st})
    meta += "".join(f'stat_metadata {{ key: {100 + i} value {{ id: '
                    f'{100 + i} name: "{k}" }} }}\n'
                    for i, k in enumerate(keys))
    ids = {n: i + 1 for i, n in enumerate(names)}

    def events(evs, stats=None):
        out = ""
        for j, (n, s, d) in enumerate(evs):
            st = "".join(f'stats {{ metadata_id: {100 + keys.index(k)} '
                         f'str_value: "{v}" }} '
                         for k, v in ((stats or [{}] * len(evs))[j]).items())
            out += (f"events {{ metadata_id: {ids[n]} offset_ps: "
                    f"{s * 1000} duration_ps: {d * 1000} {st}}}\n")
        return out
    lines = (f'lines {{ id: 1 name: "XLA Modules"\n{events(modules)}}}\n'
             if modules else "")
    text = (f'planes {{ id: 1 name: "/device:TPU:0"\n{lines}'
            f'lines {{ id: 2 name: "XLA Ops"\n{events(ops, stats)}}}\n'
            f'{meta}}}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n'
            f'lines {{ id: 1 name: "python"\n{events(host)}}}\n{meta}}}\n')
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_trace_puts_ops_down_to_their_program(tmp_path):
    """Operation names collide between the prefill and the decode: each is
    put down to the program run that holds its start, overlapping
    operations count once, and only runs that start in the window count."""
    ops = [("%fusion.1", 100, 300), ("%fusion.2", 200, 300),   # overlap
           ("%fusion.3", 600, 100),                            # not KDA
           ("%fusion.1", 1100, 200),
           ("%fusion.1", 2100, 500), ("%fusion.9", 2700, 100),  # prefill
           ("%fusion.1", 9900, 500)]                   # clipped at the end
    modules = [("jit_decode_step(7)", 50, 900),
               ("jit_decode_step(7)", 1050, 500),
               ("jit__prefill_row(8)", 2000, 1000),
               ("jit_decode_step(7)", 9800, 800),
               ("jit_decode_step(7)", 10_500, 100)]            # after
    trace = kda_roofline.read_xplane(str(_xspace(
        tmp_path / "t.xplane.pb", ops, modules)))
    secs, runs = kda_roofline.program_time(trace, "decode",
                                           {"fusion.1", "fusion.2"})
    assert runs == 3
    assert secs == pytest.approx((400 + 200 + 100) / 1e9)
    secs, runs = kda_roofline.program_time(trace, "prefill",
                                           {"fusion.1", "fusion.9"})
    assert (runs, secs) == (1, pytest.approx(600 / 1e9))
    secs, _ = kda_roofline.program_time(trace, "decode", None)
    assert secs == pytest.approx((400 + 100 + 200 + 100) / 1e9)
    hlo = ("  %fusion.1 = f32[4]{0} fusion(%p), metadata={op_name="
           '"jit(decode_step)/nugget_block_kda/mul"}\n'
           "  %fusion.2 = f32[4]{0} fusion(%p), metadata={op_name="
           '"jit(decode_step)/nugget_block_kda/add"}\n'
           "  %fusion.3 = f32[4]{0} fusion(%p), metadata={op_name="
           '"jit(decode_step)/nugget_block_moe/mul"}\n')
    conf = common.read_json(common.BENCH, "configs", f"{CONFIG}.json")
    rec = kda_roofline.record(trace, {"decode": hlo, "prefill": hlo}, conf,
                              128, 2048, "TPU v5 lite")
    assert rec["decode"]["runs"] == 3 and rec["decode"]["kda_ops"] == 2
    assert rec["decode"]["device_s"] == pytest.approx(700 / 1e9 / 3)
    assert rec["kda_share_of_decode_pct"] == pytest.approx(700 / 800 * 100)
    read = common.load_module("metrics", "kda_decode_roofline_pct").read
    assert read({"kda": rec}) == pytest.approx(
        100 * rec["decode"]["roofline_s"] / rec["decode"]["device_s"])
    json.dumps(rec)


def test_trace_without_a_module_line(tmp_path):
    """Where the TPU plane has no ``XLA Modules`` line, an operation is
    its program's by the module its stats name, is counted for either
    program where they name none, and runs are the engine's spans of that
    kind in the window."""
    ops = [("%fusion.1", 100, 300), ("%fusion.1", 2100, 500),
           ("%fusion.2", 3000, 100)]
    stats = [{"hlo_module": "jit_decode_step"},
             {"hlo_module": "jit__prefill_row"}, {"tf_op": "x"}]
    host = [("serve.decode", 50, 900), ("serve.decode", 1000, 500),
            ("serve.prefill", 2000, 1000), ("serve.decode", 20_000, 10)]
    trace = kda_roofline.read_xplane(str(_xspace(
        tmp_path / "t.xplane.pb", ops, [], host=host, stats=stats)))
    assert "XLA Modules" not in trace["lines"]
    assert trace["op_stats"] == ["hlo_module"]
    names = {"fusion.1", "fusion.2"}
    assert kda_roofline.program_time(trace, "decode", names) == (
        pytest.approx(400 / 1e9), 2)
    assert kda_roofline.program_time(trace, "prefill", names) == (
        pytest.approx(600 / 1e9), 1)
