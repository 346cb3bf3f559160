"""The benchmark's files against its contract, discovery by name, and the
entry point's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _tiny import ROOT
from bench import common

BM = common.read_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in BM["end_to_end"]]
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workload_files_exist(w):
    cell = common.Cell.load(w["name"])
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert os.path.exists(os.path.join(common.BENCH, "kinds",
                                       f"{cell.spec['kind']}.py"))
    for sub in ("program", "reference"):
        assert os.path.exists(os.path.join(common.BENCH, sub,
                                           f"{cell.family}.py"))
    assert any(m["name"] != "setup_s" for m in cell.end_to_end)
    assert cell.per_layer and set(cell.spec["limits"])
    for m in cell.per_layer:
        assert callable(common.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    conf = common.read_json(ROOT, c["file"])
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert any(w["config"] == c["name"] for w in BM["workloads"])


def test_new_cell_is_found_from_new_files_only(tmp_path):
    """A configuration, a traffic mix, a cell, a window kind and a per-layer
    metric added as new files plus new entries, no file edited."""
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads(json.dumps(BM))
    b = tmp_path / "bench"
    (b / "configs" / "new-model.json").write_text(json.dumps(
        dict(common.read_json(common.BENCH, "configs", "qwen3-1.7b.json"),
             name="new-model")))
    (b / "traffic" / "new_mix.json").write_text('{"rate_per_s": 1}')
    (b / "workloads" / "new-model.new.json").write_text(
        '{"kind": "newkind", "limits": {"x": 1}}')
    (b / "kinds" / "newkind.py").write_text("def run(*a):\n    return 'ran'\n")
    (b / "metrics" / "new_metric.x.py").write_text(
        "def read(record):\n    return record.get('x')\n")
    bm["configs"].append(dict(bm["configs"][0], name="new-model",
                              file="bench/configs/new-model.json"))
    bm["workloads"].append({"name": "new-model.new", "config": "new-model",
                            "traffic": "new_mix", "chips": 1, "why": "t"})
    bm["per_layer"].append(dict(bm["per_layer"][0], name="new_metric.x",
                                workloads=["new-model.new"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    code = ("import sys; sys.path.insert(0, '.');"
            "from bench import common;"
            "c = common.Cell.load('new-model.new');"
            "print(c.kind().run(), c.traffic['rate_per_s'],"
            " [m['name'] for m in c.per_layer],"
            " common.load_module('metrics', 'new_metric.x').read({'x': 3}))")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["ran", "1", "['new_metric.x']", "3"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    name = BM["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed",
         "2147483749", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_tpu():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_needs_the_program(tmp_path):
    """A checkout holding only the benchmark fails and prints no result."""
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout.strip() == ""
