"""Find a serving cell's knee: the highest arrival rate its engine sustains.

    python3 bench/sweep.py --workload <cell> --rates 6,8,10 --seconds 20

One process builds and warms the cell's engine once, then offers the cell's
traffic at each rate in turn and prints one JSON line per rate: the time to
first token (median and 95th percentile), the seconds the engine needed
after the last arrival to drain its queue, and the output tokens completed
per second.  A rate is sustained while the drain stays near the longest
request's decode time and does not grow with the window.  Run it on the
chip when a serving cell is defined; the cell then fixes its rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import arrivals, common
    from bench.kinds import serve
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import numpy as np
    cell = common.Cell.load(args.workload)
    common.device(cell.chips)
    eng, init = serve.build(cell)
    params = init(jax.random.PRNGKey(args.seed))
    vocab = cell.config["vocab_size"]
    slots = cell.spec["slots"]
    serve.warm(eng, params, arrivals.prompts(cell.traffic, vocab, args.seed,
                                             slots + 1))
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        sched = arrivals.schedule(traffic, args.seconds)
        served = serve.Served(sched, arrivals.prompts(traffic, vocab,
                                                      args.seed, len(sched)))
        eng.reset()
        wall = serve.serve(eng, params, served, args.seconds)
        ttft = served.ttft_s()
        toks = sum(len(o) for o in served.outputs.values())
        print(json.dumps({
            "rate_per_s": rate, "requests": len(sched),
            "ttft_p50_ms": float(np.median(ttft)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "drain_s": wall - sched[-1][0],
            "tokens_per_s": toks / wall,
            "decode_step_ms": float(np.mean([d for _, d in served.decode_s]))
            * 1e3,
            "prefill_ms": float(np.mean([d for _, d in served.prefill_s]))
            * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
