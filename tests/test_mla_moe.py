"""The ``mla_moe`` family (DeepSeek-V3 block) at a tiny size: absorbed
decode attention against the decompressed form, the held-expert layer
(no token dropped) and its cost, and the serving engine's one read per
step with the router counts it carries."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models.model_zoo import build_model
from repro.serve.engine import Request, ServeEngine


def _cfg(n_experts=16, n_held=4, held_first=0, layers=3):
    """Moonlight's block at a tiny size, in float32: 4 heads of 16 (nope)
    + 8 (rope), latent 32, a router over ``n_experts`` of which
    ``n_held`` are held here."""
    cfg = reduced(get_config("moonlight-16b-a3b"), n_layers=layers)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=n_experts, top_k=6, n_held=n_held,
        held_first=held_first))


def test_absorbed_decode_equals_decompressed(rng_key):
    """Decode's absorbed attention over the latent cache gives each
    position what prefill's decompressed attention gives it, to float32
    rounding (the two orders of the same sums)."""
    cfg = _cfg()
    a = cfg.attn
    m = build_model(cfg)
    p = jax.tree.map(lambda x: x[0], m.init(rng_key)["layers"])["attn"]
    b, s = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q_nope, q_pe, c_kv, k_pe = A.mla_project(p, a, x, pos, jnp.float32)
    want = A.mla_prefill(p, a, m.dims.layout, q_nope, q_pe, c_kv, k_pe, pos,
                         jnp.float32, impl="reference")
    for t in (0, 5, s - 1):
        got = A.mla_decode(p, a, q_nope[:, t:t + 1], q_pe[:, t:t + 1], c_kv,
                           k_pe, jnp.full((b,), t + 1), jnp.float32)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, t]),
                                   rtol=1e-4, atol=1e-5)


def _dense_experts(params, cfg, x):
    """The held experts' part by the plain rule: every held expert on
    every token, weighted by its gate where the router chose it."""
    mc = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    top_e, top_g = M.route_sigmoid(params["router"], xt, mc)
    gates = jnp.sum(jax.nn.one_hot(top_e, mc.n_experts) * top_g[..., None],
                    1)[:, mc.held_first:mc.held_first + mc.n_local]
    y = 0.0
    for j in range(mc.n_local):
        h = (jax.nn.silu(xt @ params["wi"][j]) * (xt @ params["wg"][j]))
        y = y + gates[:, j:j + 1] * (h @ params["wo"][j])
    return y.reshape(x.shape)


@pytest.mark.parametrize("hot", [False, True])
def test_held_layer_drops_no_token(hot, rng_key):
    """With every token routed to one held expert (its correction bias
    raised), that expert takes the whole batch and nothing is dropped;
    either way the layer is the plain rule plus the shared experts."""
    cfg = _cfg(n_held=4, held_first=4)
    m = build_model(cfg)
    p = jax.tree.map(lambda x: x[0], m.init(rng_key)["layers"])["moe"]
    if hot:
        p["router"]["score_bias"] = p["router"]["score_bias"].at[5].set(9.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 40, cfg.d_model))
    y, aux = jax.jit(lambda p, x: M.moe_held(p, cfg, x))(p, x)
    want = _dense_experts(p, cfg, x) + L.mlp(p["shared"], x, cfg.act,
                                             x.dtype)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    counts = np.asarray(aux["expert_tokens"])
    assert int(aux["dropped_tokens"]) == 0
    assert counts.sum() == 160 * 6
    assert int(aux["held_tokens"]) == counts[4:8].sum()
    if hot:
        assert counts[5] == 160          # every token, 16 times the mean


def _moe_flops(n_experts, n_held):
    cfg = _cfg(n_experts=n_experts, n_held=n_held)
    specs = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0)))["layers"]
    p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                     specs)["moe"]
    x = jax.ShapeDtypeStruct((8, 64, cfg.d_model), jnp.float32)
    c = jax.jit(lambda p, x: M.moe_held(p, cfg, x)[0]).lower(p, x).compile()
    return c.cost_analysis()["flops"]


def test_layer_flops_follow_the_held_experts():
    """The compiled layer's cost grows with the experts held here; a wider
    router adds its own product and nothing per held expert."""
    f = {(e, h): _moe_flops(e, h) for e in (16, 64) for h in (2, 4, 8)}
    step = f[16, 4] - f[16, 2]
    assert step > 0
    assert f[16, 8] - f[16, 4] == pytest.approx(2 * step, rel=0.1)
    router = 2 * 8 * 64 * _cfg().d_model * (64 - 16)
    for h in (2, 4, 8):
        assert 0 < f[64, h] - f[16, h] < 1.2 * router


@pytest.fixture(scope="module")
def served():
    """Each family's engine serving four requests with tracing on."""
    out = {}
    for name, cfg in (("dense", reduced(get_config("qwen3-1.7b"))),
                      ("mla_moe", _cfg())):
        eng = ServeEngine(cfg, batch=2, max_seq=40, prefill_len=16)
        params = eng.model.init(jax.random.PRNGKey(5))
        real, reads = jax.device_get, []

        def counting(x):
            reads.append(eng.iterations)
            return real(x)
        t = obs.configure(trace=True)
        jax.device_get = counting
        try:
            eng.run(params, [Request(i, np.arange(16) + i, 3 + i)
                             for i in range(4)])
        finally:
            jax.device_get = real
            obs.configure(trace=False)
        out[name] = (eng, [e for e in t.events() if e["ph"] == "X"], reads)
    return out


@pytest.mark.parametrize("family", ["dense", "mla_moe"])
def test_one_read_per_step(served, family):
    """Every engine iteration makes exactly one device-to-host transfer,
    inside its one ``serve.read_*`` span, an expert model's router counts
    included."""
    eng, evs, reads = served[family]
    assert sorted(reads) == list(range(eng.iterations))
    steps = [e for e in evs if e["name"] == "serve.step"]
    for s in steps:
        inside = [e for e in evs if e["name"].startswith("serve.read_")
                  and s["ts"] <= e["ts"] <= s["ts"] + s["dur"]]
        assert len(inside) == 1


def test_router_counts_on_spans_and_intervals(served):
    """An expert model's steps carry ``held_tokens`` and
    ``expert_load_max`` on their prefill and decode spans, and give the
    interval builder the router's counts over all its experts; a dense
    model's give neither."""
    eng, evs, _ = served["mla_moe"]
    mc = eng.cfg.moe
    spans = [e["args"] for e in evs
             if e["name"] in ("serve.prefill", "serve.decode")]
    assert len(spans) == eng.iterations
    for a in spans:
        assert 0 <= a["expert_load_max"] <= a["held_tokens"]
    log = eng.builder.step_log
    assert [k for k, _ in log] == eng.kinds_log
    for (kind, dyn), a in zip(log, spans):
        assert set(dyn) == {"expert_tokens", "dropped_tokens"}
        assert dyn["expert_tokens"].shape == (mc.n_experts,)
        assert int(dyn["dropped_tokens"]) == 0
        held = dyn["expert_tokens"][:mc.n_local]
        assert (held.sum(), held.max()) == (a["held_tokens"],
                                            a["expert_load_max"])
    # one layer's tokens, k choices each, over the expert layers
    prefill = log[eng.kinds_log.index("prefill")][1]
    assert prefill["expert_tokens"].sum() == 16 * 6 * (eng.cfg.n_layers - 1)
    eng_d, evs_d, _ = served["dense"]
    assert all(dyn is None for _, dyn in eng_d.builder.step_log)
    assert all("held_tokens" not in e["args"] for e in evs_d)


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_mla_marker_locatable(path):
    """``nugget_block_mla`` labels latent attention in the compiled
    prefill (decompressed) and decode (absorbed), and
    ``nugget_block_moe`` the held-expert layer."""
    from repro.core.hlo_analysis import find_scope_labels
    m = build_model(_cfg())
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: m.init_cache(2, 24))
    if path == "prefill":
        fn, arg = m.prefill, {"tokens": jax.ShapeDtypeStruct((2, 16),
                                                             jnp.int32)}
    else:
        fn, arg = m.decode_step, jax.ShapeDtypeStruct((2, 1), jnp.int32)
    hlo = jax.jit(fn).lower(params, arg, cache).compile().as_text()
    assert find_scope_labels(hlo, "nugget_block_mla")
    assert find_scope_labels(hlo, "nugget_block_moe")
