"""The serving engine's phase spans at the tiny serving cell's size: the
phases of each iteration with request ids, the benchmark's untraced runs
leaving them off, an idle gap of a profiler trace put down to them, and the
spans as a TPU v5e profiler trace recorded them."""
import os

import jax
import pytest

from _tiny import serve_cell
from bench import common, trace_reduce
from bench.kinds import serve
from repro import obs
from repro.serve.engine import Request, ServeEngine

SEED = 2**31 + 23
RECORDED = os.path.join(common.BENCH, "testdata",
                        "serve_spans.events.json.gz")
PHASES = {"serve.admit", "serve.prefill", "serve.insert", "serve.read_first",
          "serve.decode", "serve.read_tokens", "serve.retire",
          "serve.read_length", "serve.meter"}


def _traced(eng, params):
    """Step ``eng`` until idle with tracing on; its span events."""
    t = obs.configure(trace=True)
    try:
        while eng.step(params):
            pass
        return [e for e in t.events() if e["ph"] == "X"]
    finally:
        obs.configure(trace=False)


@pytest.fixture(scope="module")
def tiny_engine():
    cell = serve_cell()
    eng, init = serve.build(cell)
    return cell, eng, init(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def engine_run(tiny_engine):
    """The tiny cell's engine serving five submitted requests with tracing
    on: the span events."""
    cell, eng, params = tiny_engine
    eng.reset()
    prompts = [[i + 1] * cell.traffic["prompt_len"] for i in range(5)]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, 3 + i))
    return _traced(eng, params)


def _iterations(evs, kind):
    """The phases, in order of start, of each ``serve.step`` of ``kind``."""
    steps = [e for e in evs if e["name"] == "serve.step"
             and e["args"]["kind"] == kind]
    return [sorted((e for e in evs if e["name"] != "serve.step"
                    and s["ts"] <= e["ts"] <= s["ts"] + s["dur"]),
                   key=lambda e: e["ts"]) for s in steps]


def test_insert_iteration_phases(engine_run):
    evs = engine_run
    its = _iterations(evs, "insert")
    assert len(its) == 5
    for phases in its:
        assert [e["name"] for e in phases] == [
            "serve.admit", "serve.prefill", "serve.insert",
            "serve.read_first", "serve.meter"]
        a = {e["name"]: e["args"] for e in phases}
        req = a["serve.admit"]["req"]
        assert a["serve.admit"]["queued_s"] >= 0
        assert {a[n]["req"] for n in ("serve.prefill", "serve.insert",
                                      "serve.read_first")} == {req}
        assert a["serve.insert"]["slot"] == a["serve.admit"]["slot"]
    assert sorted(p[0]["args"]["req"] for p in its) == list(range(5))


def test_decode_iteration_phases(engine_run):
    evs = engine_run
    its = _iterations(evs, "decode")
    assert its
    for phases in its:
        names = [e["name"] for e in phases]
        assert names[:3] == ["serve.decode", "serve.read_tokens",
                             "serve.retire"]
        assert names[-1] == "serve.meter"
        assert set(names[3:-1]) <= {"serve.read_length"}
        a = {e["name"]: e["args"] for e in phases}
        assert 1 <= a["serve.decode"]["batch"] <= serve_cell().spec["slots"]
        slots = [e["args"]["slot"] for e in phases
                 if e["name"] == "serve.read_length"]
        assert len(set(slots)) == len(slots)
    done = [i for e in evs if e["name"] == "serve.retire"
            for i in e["args"]["done"]]
    assert sorted(done) == list(range(5))


def test_queue_wait_only_for_submitted_requests(engine_run, tiny_engine):
    """``queued_s`` is the wait since ``submit``; a request put on the queue
    without it has no submission time, and its admit span no wait."""
    assert len([e for e in engine_run if e["name"] == "serve.admit"
                and e["args"]["queued_s"] >= 0]) == 5
    cell, eng, params = tiny_engine
    eng.reset()
    eng.queue.append(Request(0, [1] * cell.traffic["prompt_len"], 2))
    admits = [e["args"] for e in _traced(eng, params)
              if e["name"] == "serve.admit"]
    assert admits == [{"req": 0, "slot": 0}]


def test_untraced_run_leaves_obs_off(monkeypatch):
    seen = []
    real = ServeEngine.step

    def step(self, params):
        seen.append(obs.enabled())
        return real(self, params)
    monkeypatch.setattr(ServeEngine, "step", step)
    serve.run(serve_cell(), SEED, 1.0, False)
    assert seen and not any(seen) and not obs.enabled()


def test_reduce_puts_a_gap_on_the_innermost_engine_phase():
    ms = 1_000_000
    events = {
        "device": {"/device:TPU:0": [("fusion.1", 0, 10 * ms),
                                     ("fusion.2", 30 * ms, 10 * ms)]},
        "host": [("bench.traced", 0, 40 * ms),
                 ("bench.step", 5 * ms, 30 * ms),
                 ("serve.step", 6 * ms, 28 * ms),
                 ("serve.retire", 8 * ms, 25 * ms),
                 ("serve.read_length", 9 * ms, 22 * ms)],
    }
    r = trace_reduce.reduce(events)
    assert r["idle_gaps"] == [["serve.read_length", pytest.approx(0.02)]]


def test_recorded_trace_with_engine_spans():
    """0.3 s of a traced run of the serving cell on one TPU v5e chip, saved
    with the engine's ``serve.*`` host events beside the harness's
    (``docs/serve_spans_bench.patch``): the spans nest on the profiler's
    host plane as the engine opens them."""
    events = trace_reduce.read_saved(RECORDED)
    r = trace_reduce.reduce(events)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.162365976)

    def inside(e, name):
        return any(s <= e[1] and e[1] + e[2] <= s + d
                   for n, s, d in events["host"] if n == name)
    engine = [e for e in events["host"] if e[0].startswith("serve.")]
    steps = [e for e in engine if e[0] == "serve.step"]
    phases = [e for e in engine if e[0] != "serve.step"]
    assert len(steps) == 8 and {e[0] for e in phases} == PHASES
    assert all(inside(e, "bench.step") for e in steps)
    assert all(inside(e, "serve.step") for e in phases)
    assert all(inside(e, "serve.retire") for e in phases
               if e[0] == "serve.read_length")
    # the engine's spans cover its iterations, so little idle time is left
    # on the harness's span around them
    idle = sum(v for _, v in r["idle_gaps"])
    assert dict(r["idle_gaps"])["bench.step"] < 0.1 * idle
