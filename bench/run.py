"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout.  The cell's files are found by name (see
``bench/common.py``).  With ``--trace 0`` the result line's ``metrics`` are
the cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
each read by ``bench/metrics/<metric>.py`` from what the run recorded, and
a ``breakdown`` of the profiler trace.  Each number compared for ``correct``
is printed beside its limit as the last lines of standard error and under
``check``, the last key of the result line, which is the last line of
standard output.  Exits 2, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def metrics_out(cell, out: dict, trace: bool) -> dict:
    from bench import common
    if not trace:
        e2e = dict(out["end_to_end"])
        e2e["setup_s"] = out["window_start"] - START
        return {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end if m["name"] in e2e}
    res = {}
    for m in cell.per_layer:
        v = common.load_module("metrics", m["name"]).read(out["record"])
        if v is not None:
            res[m["name"]] = {"value": v, "unit": m["unit"]}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, ".bench_out", "tpu_logs"))

    from bench import common
    cell = common.Cell.load(args.workload)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    # every program, the small eager ones too, comes from the cache after
    # a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        dev = common.device(cell.chips)
    except common.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    out = cell.kind().run(cell, args.seed, args.seconds, bool(args.trace))
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"],
              "metrics": metrics_out(cell, out, bool(args.trace)),
              "device": dev}
    if args.trace:
        traces = out["record"].get("traces", {})
        if traces:
            t = next(iter(traces.values()))
            dev["busy_s"], dev["window_s"] = t["busy_s"], t["window_s"]
            result["breakdown"] = {"device_ops": t["device_ops"],
                                   "idle_gaps": t["idle_gaps"]}
    checks = out["checks"]
    result["correct"] = common.correct(checks)
    result["check"] = checks
    for k, v in out.get("notes", {}).items():
        print(f"note {k} {v}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
