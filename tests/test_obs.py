"""Unit tests for the ``repro.obs`` tracing + metrics + logging layer."""
import json
import logging
import threading

import pytest

from repro import obs
from repro.obs import (MetricsRegistry, Tracer, chrome_trace, read_events,
                       span_summary)
from repro.obs.log import KVFormatter, resolve_level, setup
from repro.launch.obs import main as obs_cli


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test gets a disabled tracer and a fresh metrics registry."""
    obs.configure(trace=False, reset_metrics=True)
    yield
    obs.configure(trace=False, reset_metrics=True)


# -- tracer -------------------------------------------------------------
def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2 is obs.NULL_SPAN
    with s1 as sp:
        sp.set(anything=True)
        sp.event("ignored")
    assert obs.tracer().events() == []


def test_disabled_span_makes_no_profiler_call(monkeypatch):
    """Disabled, a span is the shared no-op and never reaches jax; enabled,
    it enters a profiler annotation of its name."""
    from repro.obs import trace
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return None
    monkeypatch.setattr(trace, "_annotation_type", lambda: Annotation)
    assert Tracer(enabled=False).span("off") is obs.NULL_SPAN
    with obs.span("off"):
        pass
    assert entered == []
    with obs.configure(trace=True).span("on"):
        pass
    assert entered == ["on"]


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    """Enabled spans appear, nested, on the host plane of a jax.profiler
    trace (here on the CPU)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    t = obs.configure(trace=True)
    with jax.profiler.trace(str(tmp_path)):
        with t.span("obs_outer", stage="x"):
            with t.span("obs_inner"):
                jax.numpy.ones(4).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs_"):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {"obs_outer", "obs_inner"}
    (o0, o1), (i0, i1) = found["obs_outer"], found["obs_inner"]
    assert o0 <= i0 < i1 <= o1
    assert [e["name"] for e in t.events()] == ["obs_inner", "obs_outer"]


def test_spans_nest_and_record_duration():
    t = obs.configure(trace=True)
    with t.span("outer", stage="profile") as outer:
        assert t.depth() == 1
        with t.span("inner"):
            assert t.depth() == 2
        outer.event("milestone", n=3)
    assert t.depth() == 0
    evs = t.events()
    names = [e["name"] for e in evs]
    # inner closes before outer; the instant event fires before outer closes
    assert names == ["inner", "outer.milestone", "outer"]
    spans = [e for e in evs if e["ph"] == "X"]
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
    outer_ev = spans[-1]
    assert outer_ev["args"]["stage"] == "profile"


def test_span_records_exception_attr():
    t = obs.configure(trace=True)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    (ev,) = t.events()
    assert ev["args"]["error"] == "ValueError"
    assert t.depth() == 0                    # stack unwound


def test_chrome_trace_export_is_loadable(tmp_path):
    t = obs.configure(trace=True)
    with t.span("stage.profile", key="abc123"):
        pass
    path = t.write_chrome(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert "traceEvents" in doc
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"M", "X"}                 # metadata + complete spans
    span = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert {"name", "ts", "dur", "pid", "tid", "args"} <= span.keys()


def test_jsonl_sink_streams_and_reads_back(tmp_path):
    t = obs.configure(trace=True, trace_dir=str(tmp_path))
    with t.span("a"):
        pass
    t.event("standalone", n=1)
    t.close()
    evs = read_events(str(tmp_path / "trace.jsonl"))
    assert [e["name"] for e in evs] == ["a", "standalone"]
    # chrome export of the same events reads back identically (minus meta)
    (tmp_path / "trace2.json").write_text(json.dumps(chrome_trace(evs)))
    assert read_events(str(tmp_path / "trace2.json")) == evs


def test_tracer_is_thread_safe():
    t = obs.configure(trace=True)

    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()                       # overlap all four workers
        for _ in range(50):
            with t.span(f"worker{i}"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = t.events()
    assert len(evs) == 200                   # no lost appends
    by_name = {f"worker{i}": 0 for i in range(4)}
    for e in evs:
        by_name[e["name"]] += 1
    assert all(v == 50 for v in by_name.values())


def test_span_summary_aggregates_by_name():
    t = obs.configure(trace=True)
    for _ in range(3):
        with t.span("x"):
            pass
    with t.span("y"):
        pass
    rows = {r["name"]: r for r in span_summary(t.events())}
    assert rows["x"]["count"] == 3 and rows["y"]["count"] == 1
    assert rows["x"]["total_ms"] >= rows["x"]["max_ms"]


# -- metrics ------------------------------------------------------------
def test_counter_gauge_histogram_snapshot():
    m = MetricsRegistry()
    m.count("c")
    m.count("c", 2)
    m.record("g", 4.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        m.observe("h", v)
    snap = m.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3}
    assert snap["g"] == {"type": "gauge", "value": 4.5}
    h = snap["h"]
    assert h["count"] == 4 and h["sum"] == 10.0 and h["mean"] == 2.5
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] in (2.0, 3.0)
    # round-trips through JSON
    assert json.loads(json.dumps(snap)) == snap


def test_histogram_window_bounds_memory_but_keeps_totals():
    m = MetricsRegistry()
    h = m.histogram("h", window=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and h.max == 99.0 and h.min == 0.0
    assert len(h._recent) == 8               # reservoir stays bounded
    assert h.quantile(0.5) >= 92.0           # quantiles track the window


def test_metric_kind_collision_raises():
    m = MetricsRegistry()
    m.count("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_report_renders_every_instrument():
    m = MetricsRegistry()
    m.count("store.hit", 5)
    m.record("train.loss", 1.25)
    m.observe("step_s", 0.5)
    rep = m.report()
    for needle in ("store.hit", "train.loss", "step_s", "counter", "gauge",
                   "histogram"):
        assert needle in rep


# -- logging ------------------------------------------------------------
def test_log_level_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
    assert resolve_level() == logging.INFO
    monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
    assert resolve_level() == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
    assert resolve_level() == logging.WARNING
    assert resolve_level("error") == logging.ERROR
    assert resolve_level("17") == 17


def test_kv_lines_are_structured(capsys):
    import io
    buf = io.StringIO()
    logger = setup(level="info", stream=buf)
    obs.log.kv("cache_hit", logger="pipeline", kind="profile",
               key="abc 123", n=3)
    line = buf.getvalue().strip()
    assert "level=info" in line
    assert "logger=repro.pipeline" in line
    assert "event=cache_hit" in line
    assert "kind=profile" in line
    assert 'key="abc 123"' in line           # values with spaces are quoted
    assert "n=3" in line
    # idempotent: re-setup replaces the handler instead of stacking
    setup(level="info", stream=buf)
    assert sum(getattr(h, "_repro_kv", False)
               for h in logger.handlers) == 1


def test_debug_suppressed_at_info(capsys):
    import io
    buf = io.StringIO()
    setup(level="info", stream=buf)
    obs.log.kv("quiet", level=logging.DEBUG)
    assert buf.getvalue() == ""


# -- trainer ring buffer ------------------------------------------------
def test_trainer_metrics_history_is_bounded():
    """_post_step keeps only the newest ``history_cap`` rows while the
    registry keeps full-run aggregates (the unbounded-growth fix)."""
    from repro.train.trainer import Trainer

    tr = object.__new__(Trainer)             # skip the expensive model build
    from collections import deque
    tr.step_times = []
    tr.slow_steps = []
    tr.straggler_factor = 3.0
    tr.metrics_history = deque(maxlen=4)
    tr._tokens_per_step = 128
    tr.builder = None
    for s in range(10):
        tr._post_step(s, 0.01, {"loss": float(s)}, {})
    assert len(tr.metrics_history) == 4
    assert [r["loss"] for r in tr.metrics_history] == [6.0, 7.0, 8.0, 9.0]
    assert tr.metrics_history[-1]["loss"] == 9.0
    m = obs.metrics()
    assert m.value("train.steps") == 10      # full-run total survives the cap
    assert m.value("train.loss") == 9.0
    assert m.snapshot()["train.step_s"]["count"] == 10


# -- CLI ----------------------------------------------------------------
def test_obs_cli_summarizes_and_merges(tmp_path, capsys):
    t = obs.configure(trace=True, trace_dir=str(tmp_path))
    with t.span("stage.profile", key="k1"):
        with t.span("intervals.analyze_batch"):
            pass
    t.close()
    obs.metrics().count("store.miss", 2)
    obs.metrics().write_json(str(tmp_path / "metrics.json"))

    assert obs_cli([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "stage.profile" in out and "intervals.analyze_batch" in out
    assert "store.miss" in out

    merged = tmp_path / "merged.json"
    assert obs_cli([str(tmp_path), "--merge-out", str(merged)]) == 0
    doc = json.loads(merged.read_text())
    assert [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"] \
        == ["intervals.analyze_batch", "stage.profile"]


def test_obs_cli_json_mode(tmp_path, capsys):
    t = obs.configure(trace=True, trace_dir=str(tmp_path))
    with t.span("a"):
        pass
    t.close()
    assert obs_cli([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["events"] == 1
    assert doc["spans"][0]["name"] == "a"


def test_obs_cli_no_traces_errors(tmp_path, capsys):
    assert obs_cli([str(tmp_path)]) == 1


# -- env configuration --------------------------------------------------
def test_configure_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert not obs.configure_from_env().enabled
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert obs.configure_from_env().enabled
    monkeypatch.setenv("REPRO_TRACE", str(tmp_path))
    t = obs.configure_from_env()
    assert t.enabled
    with t.span("x"):
        pass
    t.close()
    assert (tmp_path / "trace.jsonl").exists()
