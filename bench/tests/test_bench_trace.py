"""Reduction of profiler traces: a hand-made one, and one recorded on a TPU
v5e in a traced run of the serving cell."""
import os

import pytest

from _tiny import ROOT
from bench import common, trace_reduce

RECORDED = os.path.join(common.BENCH, "testdata", "serve.events.json.gz")


def test_reduce_by_hand():
    ms = 1_000_000
    events = {
        "device": {"/device:TPU:0": [("fusion.1", 10 * ms, 20 * ms),
                                     ("fusion.1", 25 * ms, 10 * ms),
                                     ("dot.2", 60 * ms, 30 * ms),
                                     ("late", 95 * ms, 50 * ms)]},
        "host": [("bench.traced", 0, 100 * ms),
                 ("bench.step", 0, 40 * ms),
                 ("bench.wait", 35 * ms, 30 * ms)],
    }
    r = trace_reduce.reduce(events)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 35] + [60, 90] + [95, 100] = 60 ms
    assert r["busy_s"] == pytest.approx(0.06)
    assert r["device_ops"] == [["dot.2", pytest.approx(0.03)],
                               ["fusion.1", pytest.approx(0.03)],
                               ["late", pytest.approx(0.005)]]
    # gaps: [0, 10] under step; [35, 60] mostly under wait; [90, 95] idle
    assert dict((k, pytest.approx(v)) for k, v in r["idle_gaps"]) == {
        "bench.step": 0.01, "bench.wait": 0.025, "idle": 0.005}


def test_reduce_without_device_or_window():
    assert trace_reduce.reduce({"device": {}, "host": []}) == {}


def test_recorded_trace():
    """0.3 s of a traced run of the serving cell on one TPU v5e chip."""
    r = trace_reduce.reduce(trace_reduce.read_saved(RECORDED))
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.155782412)
    assert len(r["device_ops"]) == trace_reduce.TOP
    assert r["device_ops"][0] == ["%while.4", pytest.approx(0.076433817)]
    assert r["idle_gaps"] == [["bench.step", pytest.approx(0.144217588)]]
