"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).
Prints ``name,us_per_call,derived`` CSV.  Select with --only substr.

The pipeline suite additionally appends its run-manifest summary (stage
wall times + cache-hit counts) to ``BENCH_pipeline.json`` so perf history
accumulates across invocations."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (bench_hook_overhead, bench_interval_overhead,
                        bench_kernels, bench_model_accuracy,
                        bench_pipeline, bench_prediction_error,
                        bench_speedup_prediction, bench_sync_scaling)
from benchmarks.common import fmt_rows

SUITES = [
    ("interval_overhead(Fig2-3)", bench_interval_overhead),
    ("sync_scaling(Fig4)", bench_sync_scaling),
    ("prediction_error(Fig5)", bench_prediction_error),
    ("hook_overhead(Fig6)", bench_hook_overhead),
    ("speedup_prediction(Fig7-10)", bench_speedup_prediction),
    ("model_accuracy(Fig11)", bench_model_accuracy),
    ("kernels", bench_kernels),
    ("pipeline(manifest)", bench_pipeline),
]

TRAJECTORY = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_pipeline.json")


def write_trajectory(path: str = TRAJECTORY) -> None:
    """Append the pipeline suite's manifest summary to the trajectory file."""
    if bench_pipeline.LAST_ENTRY is None:
        return
    history = []
    if os.path.exists(path):
        with open(path) as f:
            history = json.load(f)
    history.append({"ts": time.time(), **bench_pipeline.LAST_ENTRY})
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"# pipeline trajectory -> {os.path.abspath(path)} "
          f"({len(history)} entries)", flush=True)
    speedup = bench_pipeline.LAST_ENTRY.get("parallel_speedup_x")
    if speedup is not None:
        print(f"# pipeline parallel speedup: {speedup:.2f}x "
              f"(workers={bench_pipeline.PARALLEL_WORKERS})", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = mod.run()
            print(fmt_rows(rows), flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failed.append(name)
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
    write_trajectory()
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
