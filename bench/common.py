"""Plumbing shared by the benchmark's entry point and window kinds: files
found by name, the device, the clock, and the profiler.

Everything of one configuration, traffic mix, window kind or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``bench/configs/<config>.json``      a configuration, as it is run
- ``bench/traffic/<traffic>.json``     a traffic mix's parameters
- ``bench/workloads/<cell>.json``      a cell: its window kind, the kind's
                                       parameters and the limits of the
                                       comparison that decides ``correct``
- ``bench/kinds/<kind>.py``            a window kind: ``run(cell, ...)``
- ``bench/metrics/<metric>.py``        a per-layer metric: ``read(record)``
- ``bench/program/<family>.py``        the program's configuration of a family
- ``bench/reference/<family>.py``      a family's plain float32 reference
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Any, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# run-time output (traces), inside the checkout and listed in .gitignore
OUT = os.path.join(ROOT, ".bench_out")


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def read_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(subdir: str, name: str):
    """``bench/<subdir>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, subdir, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{subdir}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""
    name: str
    chips: int
    spec: Dict            # bench/workloads/<name>.json
    config: Dict          # bench/configs/<config>.json
    traffic: Dict         # bench/traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell

    @staticmethod
    def load(name: str, benchmark: Optional[Dict] = None) -> "Cell":
        bm = benchmark or read_json(ROOT, "BENCHMARK.json")
        entries = [w for w in bm["workloads"] if w["name"] == name]
        if not entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = entries[0]

        def mine(m):
            return name in m.get("workloads", [name])

        return Cell(name, int(w["chips"]),
                    read_json(BENCH, "workloads", f"{name}.json"),
                    read_json(BENCH, "configs", f"{w['config']}.json"),
                    read_json(BENCH, "traffic", f"{w['traffic']}.json"),
                    [m for m in bm["end_to_end"] if mine(m)],
                    [m for m in bm["per_layer"] if mine(m)])

    @property
    def family(self) -> str:
        return self.config["family"]

    def kind(self):
        return load_module("kinds", self.spec["kind"])

    def reference(self):
        return load_module("reference", self.family)

    def program_config(self):
        return load_module("program", self.family).program_config(
            self.config)


def correct(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number compared is finite and within its limit."""
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


# -- the device --------------------------------------------------------------
def device(chips: int) -> Dict[str, Any]:
    """The chips JAX found; raises :class:`NoDevice` unless they are at
    least ``chips`` TPUs.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {d.platform} device(s) "
                       f"({d.device_kind!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- the profiler ------------------------------------------------------------
def span(name: str):
    """A host span in the profiler's trace (``jax.profiler.TraceAnnotation``;
    costs next to nothing while no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


@contextlib.contextmanager
def traced(label: str, record: Dict):
    """Take a profiler trace of the block and put its reduction (see
    ``trace_reduce``) into ``record["traces"][label]``."""
    import jax
    from bench import trace_reduce
    out = os.path.join(OUT, "trace", label)
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    try:
        with span("traced"):
            yield
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    if files:
        events = trace_reduce.load(files[0])
        trace_reduce.save(events, os.path.join(OUT, f"{label}.events.json.gz"))
        record.setdefault("traces", {})[label] = trace_reduce.reduce(events)
    shutil.rmtree(out, ignore_errors=True)
