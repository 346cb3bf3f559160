"""Readings that set the limits of the comparison that decides ``correct``.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 8 [--out FILE]

For a serving cell, one process builds and warms the engine once and, for
each seed: makes that seed's weights, serves a short window of the cell's
traffic at the cell's load, samples finished requests as a run does, and
prints one JSON line with the widest ``logit_gap`` of the served tokens
(the program's reading) and that of the tokens the reference computed in
fp8 puts first at the same positions (the control's reading), and whether
each side is ``correct`` by the run's own comparison at the cell's limit.
The limit lies above the largest program reading and below the smallest
control reading.  The benchmark's own runs never run the control.

    python3 bench/calibrate.py --config <config> --traffic <traffic> \
        --seeds 1,2,3 [--out FILE]

For a training configuration (no cell needed), prints for each seed the
readings of ``train_check.compare`` for: the program's first three steps
(``program``); the same program with its cross-entropy computed by
``jax.nn.logsumexp`` (``program_logsumexp_ce``, a second path of the
program); the reference computed in fp8 (``control``); and the reference
with half of the batch left out (``half_batch``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serve_readings(cell, seeds, seconds, emit) -> None:
    import jax
    from bench import arrivals, common
    from bench.kinds import serve
    eng, init = serve.build(cell)
    vocab, slots = cell.config["vocab_size"], cell.spec["slots"]
    sched = arrivals.schedule(cell.traffic, seconds)
    warmed = False
    for seed in seeds:
        params = init(jax.random.PRNGKey(seed))
        prompts = arrivals.prompts(cell.traffic, vocab, seed,
                                   len(sched) + slots + 1)
        eng.reset()
        if not warmed:
            serve.warm(eng, params, prompts[len(sched):])
            warmed = True
        served = serve.Served(sched, prompts[:len(sched)])
        serve.serve(eng, params, served, seconds)
        del params
        eng.cache = None
        ids = serve.sample(served, seed, cell.spec["sample_requests"])
        prog = serve.reference_gaps(cell, seed, served, ids)
        ctrl = serve.reference_gaps(cell, seed, served, ids, control=True)
        emit({"seed": seed, "requests": len(served.outputs),
              "tokens_compared": int(prog.size),
              "program_logit_gap": float(prog.max()),
              "control_logit_gap": float(ctrl.max()),
              # each side through the run's own comparison, at the cell's
              # committed limit
              "program_correct": common.correct(serve.checks(cell, prog)),
              "control_correct": common.correct(serve.checks(cell, ctrl)),
              "program_p99": float(sorted(prog)[int(0.99 * (prog.size - 1))]),
              "control_median": float(sorted(ctrl)[prog.size // 2])})


def train_readings(config: str, traffic: str, seeds, emit) -> None:
    import jax
    import jax.numpy as jnp
    from bench import common, train_check
    from bench.corpus import Corpus
    from bench.reference import train
    from repro.models import model_zoo
    conf = common.read_json(common.BENCH, "configs", f"{config}.json")
    t = common.read_json(common.BENCH, "traffic", f"{traffic}.json")
    fam = common.load_module("reference", conf["family"])
    arch = common.load_module("program", conf["family"]).program_config(conf)

    def logsumexp_ce(logits, labels, vocab_size, *, z_loss=1e-4):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        nll = lse - jnp.take_along_axis(lf, labels[..., None], -1)[..., 0]
        return jnp.mean(nll) + z_loss * jnp.mean(jnp.square(lse)), nll

    for seed in seeds:
        corpus = Corpus(t, conf["vocab_size"], seed, 3)
        batches = [corpus.batch_at(i) for i in range(3)]
        ref = train.train_readings(fam, conf, seed, batches, t["optimizer"])
        row = {"seed": seed, "config": config, "traffic": traffic,
               "reference_loss": ref["loss"]}
        prog = train_check.program_readings(arch, corpus, seed,
                                            t["optimizer"])
        row["program"] = train_check.compare(prog, ref, by_leaf=True)
        row["program_norms"] = prog
        row["reference_norms"] = ref
        ce = model_zoo.cross_entropy
        model_zoo.cross_entropy = logsumexp_ce
        try:
            row["program_logsumexp_ce"] = train_check.compare(
                train_check.program_readings(arch, corpus, seed,
                                             t["optimizer"]), ref,
                by_leaf=True)
        finally:
            model_zoo.cross_entropy = ce
        row["control"] = train_check.compare(train.train_readings(
            fam, conf, seed, batches, t["optimizer"], precision="fp8"), ref)
        row["half_batch"] = train_check.compare(train.train_readings(
            fam, conf, seed, batches, t["optimizer"], half_batch=True), ref)
        emit(row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import common
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    common.device(1)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    if args.workload:
        cell = common.Cell.load(args.workload)
        serve_readings(cell, seeds, args.seconds,
                       lambda row: emit(dict(row, workload=cell.name)))
    else:
        train_readings(args.config, args.traffic, seeds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
