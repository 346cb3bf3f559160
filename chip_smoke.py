#!/usr/bin/env python3
"""Chip smoke test: drive the system's main path once on one TPU chip.

One process runs these phases in order; any failure exits non-zero before
the result line is printed.

  device    ``jax.devices()`` must report a TPU.
  kernels   ``flash_attention`` and ``flash_decode`` at qwen3-1.7b widths and
            ``ssd`` at mamba2-780m widths, compiled by Mosaic (never
            interpreted on a TPU), against the oracles in ``kernels/ref.py``.
  pipeline  ``Pipeline(...).run()`` on qwen3-1.7b at its published widths,
            depth cut to ``PIPELINE_LAYERS``, into a fresh artifact store:
            every stage computes, the profile has intervals, every platform
            has replay results and the validation numbers are finite.
  serve     ``ServeEngine`` on qwen3-1.7b at all 28 layers: every request
            finishes, and prefill followed by cached decode gives the logits
            of ``model.forward`` over the same tokens.

Wall time, compile time and ``peak_bytes_in_use`` printed per phase are
bring-up diagnostics, not benchmark results.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Usage:
    python3 chip_smoke.py [--out DIR]

Files go under ``--out`` (default ``chiprun_out/chip_smoke``) and JAX's
compilation cache (``repro.launch.compile_cache``).  Tests force the CPU;
they drive the phase functions at ``reduced()`` size.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models.model_zoo import build_model  # noqa: E402
from repro.models.ssm import ssm_dims  # noqa: E402
from repro.pipeline import (Artifact, ArtifactStore, Pipeline,  # noqa: E402
                            PipelineConfig)
from repro.serve import ServeEngine, SyntheticRequests  # noqa: E402

# Tolerances, as max|got - want| / max|want| over the whole output.
# bf16 attention output: one bf16 step at the largest value is 2^-8 ≈ 3.9e-3
# of it; the kernels may also feed f32 matmuls through bf16 MXU passes.
# 2e-2 allows about five steps.
ATTN_TOL = 2e-2
# SSD in f32 against the sequential f32 oracle (run at "highest" matmul
# precision): the chunked form sums in another order, and on a TPU its
# matmuls at default precision take bf16 passes (2^-9 per operand).
SSD_TOL = 1e-2
# Serving logits in bf16 through 28 layers: the cached path (decode
# attention over the cache) and the full forward (chunked attention) round
# activations at different points, and the residual stream compounds it.
# The same comparison at these widths on a CPU differed by 3.1% of the
# largest logit; a wrong cache position or length differs by O(100%).
LOGIT_TOL = 0.10

# Depth of the pipeline phase's qwen3-1.7b (published: 28).  The whole
# model's train state (bf16 params, f32 master, Adam m and v: 14 bytes a
# parameter, about 24 GB) does not fit one 16 GB chip; at 6 layers, seq
# 2048 and batch 2 the donated train step peaks at 12.1 GB when compiled
# for a v5e.
PIPELINE_LAYERS = 6


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.isfinite(got).all()), "non-finite output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# -- diagnostics ---------------------------------------------------------
class CompileClock:
    """Sums JAX's backend-compile durations (monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total_s = 0.0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.total_s += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


def run_phase(name: str, clock: CompileClock, fn: Callable, *args,
              **kwargs) -> Any:
    print(f"[{name}] start", flush=True)
    t0, c0 = time.perf_counter(), clock.total_s
    out = fn(*args, **kwargs)
    print(f"[{name}] passed: wall {time.perf_counter() - t0:.1f} s, "
          f"backend compile {clock.total_s - c0:.1f} s, device "
          f"peak_bytes_in_use since start {peak_bytes()} "
          "(bring-up diagnostics, not benchmark results)", flush=True)
    return out


# -- phases --------------------------------------------------------------
def phase_device() -> Dict[str, Any]:
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform {d.platform}, device_kind {d.device_kind!r}, "
          f"count {len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {len(devs)} {d.platform} "
                           f"device(s) ({d.device_kind!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _compiled(fn: Callable, *args) -> Callable:
    """Compile ``fn`` and check a kernel is compiled in exactly when the
    backend is a TPU (``ops.interpret_mode`` is the switch)."""
    compiled = jax.jit(fn).lower(*args).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    check(has_kernel == (not ops.interpret_mode()),
          f"kernel compiled in: {has_kernel}, backend "
          f"{jax.default_backend()}")
    return compiled


def _reference(fn: Callable, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def phase_kernels(attn_cfg: ArchConfig, ssm_cfg: ArchConfig, *, seq: int,
                  decode_batch: int, decode_cache: int,
                  seed: int = 0) -> Dict[str, float]:
    """The three Pallas kernels at ``attn_cfg``'s attention widths and
    ``ssm_cfg``'s SSD widths, against ``kernels/ref.py``."""
    a = attn_cfg.attn
    h, kv, hd = a.n_heads, a.n_kv_heads, a.head_dim
    g = h // kv
    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    errs = {}

    q = jax.random.normal(ks[0], (1, seq, h, hd), bf16)
    k = jax.random.normal(ks[1], (1, seq, kv, hd), bf16)
    v = jax.random.normal(ks[2], (1, seq, kv, hd), bf16)
    attn = _compiled(lambda q, k, v: ops.flash_attention(q, k, v, group=g),
                     q, k, v)
    errs["flash_attention"] = rel_err(attn(q, k, v), _reference(
        lambda q, k, v: ref.flash_attention_ref(q, k, v, group=g), q, k, v))

    b, s = decode_batch, decode_cache
    q = jax.random.normal(ks[3], (b, 1, h, hd), bf16)
    kc = jax.random.normal(ks[4], (b, s, kv, hd), bf16)
    vc = jax.random.normal(ks[5], (b, s, kv, hd), bf16)
    lens = jax.random.randint(ks[6], (b,), 1, s + 1)
    dec = _compiled(lambda *xs: ops.flash_decode(*xs, group=g),
                    q, kc, vc, lens)
    errs["flash_decode"] = rel_err(dec(q, kc, vc, lens), _reference(
        lambda *xs: ref.flash_decode_ref(*xs, group=g), q, kc, vc, lens))

    sc = ssm_cfg.ssm
    _, nh = ssm_dims(ssm_cfg)
    xh = jax.random.normal(ks[7], (1, seq, nh, sc.head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[8], (1, seq, nh)))
    A = -jnp.exp(jax.random.normal(ks[9], (nh,)))
    Bp = jax.random.normal(ks[10], (1, seq, sc.d_state))
    Cp = jax.random.normal(ks[11], (1, seq, sc.d_state))
    ssd = _compiled(lambda *xs: ops.ssd(*xs, chunk=sc.chunk),
                    xh, dt, A, Bp, Cp)
    y, h_fin = ssd(xh, dt, A, Bp, Cp)
    y_ref, h_ref = _reference(ref.ssd_ref, xh, dt, A, Bp, Cp)
    errs["ssd"] = max(rel_err(y, y_ref), rel_err(h_fin, h_ref))

    for name, err in errs.items():
        tol = SSD_TOL if name == "ssd" else ATTN_TOL
        print(f"[kernels] {name}: max|err|/max|ref| {err:.3e} "
              f"(tolerance {tol})", flush=True)
        check(err <= tol, f"{name} error {err:.3e} > {tol}")
    return errs


def phase_pipeline(cfg: PipelineConfig, store_root: str) -> Dict[str, Any]:
    """``Pipeline(cfg).run()`` into a fresh store; checks every stage
    computed and the run produced a usable profile, replays and a finite
    validation."""
    shutil.rmtree(store_root, ignore_errors=True)
    arch = cfg.base_cfg()
    print(f"[pipeline] {arch.name}: {arch.n_layers} layers, d_model "
          f"{arch.d_model}, d_ff {arch.d_ff}, vocab {arch.vocab_size}, "
          f"seq {cfg.seq_len}, batch {cfg.batch}, steps {cfg.steps}, "
          f"platforms {list(cfg.platforms)}", flush=True)
    manifest = Pipeline(cfg, store_root).run()
    with open(os.path.join(os.path.dirname(store_root),
                           "pipeline_manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1, default=str)

    for st in manifest["stages"]:
        print(f"[pipeline] stage {st['stage']}: cache_hit {st['cache_hit']}, "
              f"wall {st['wall_s']:.1f} s", flush=True)
        check(not st["cache_hit"], f"stage {st['stage']} was a cache hit")
    ft = manifest["fault_tolerance"]
    check(ft["retries"] == 0 and ft["timeouts"] == 0,
          f"stages retried or timed out: {ft}")

    stages = {st["stage"]: st for st in manifest["stages"]}
    store = ArtifactStore(store_root)

    def artifact(name: str) -> Artifact:
        st = stages[name]
        return Artifact(st["kind"], st["key"], st["path"], {}, [])

    profile = store.read_profile(artifact("profile"))
    check(profile.n_intervals > 0, "profile has no intervals")
    replays = {p: store.read_json(artifact(f"replay@{p}"),
                                  "replay.json")["results"]
               for p in cfg.platforms}
    for p, results in replays.items():
        check(len(results) > 0, f"no replay results on {p}")
        check(all(math.isfinite(r["region_time_s"]) and r["region_time_s"] > 0
                  for r in results), f"bad replay region time on {p}")

    metrics = manifest["metrics"]
    numbers = [x for p in metrics["platforms"].values() for x in p.values()]
    numbers += [e["abs_speedup_error"] for e in metrics["speedup_errors"]]
    check(len(metrics["platforms"]) == len(cfg.platforms)
          and all(math.isfinite(x) for x in numbers),
          f"validation numbers not finite: {metrics['platforms']}")
    print(f"[pipeline] {profile.n_intervals} intervals, "
          f"{[len(r) for r in replays.values()]} replays per platform, "
          f"validation {json.dumps(metrics['platforms'])}", flush=True)
    return {"intervals": profile.n_intervals,
            "replays": {p: len(r) for p, r in replays.items()}}


def phase_serve(cfg: ArchConfig, *, batch: int, max_seq: int,
                prefill_len: int, requests: int, mean_new: int,
                check_tokens: int, seed: int = 0) -> Dict[str, Any]:
    """``ServeEngine`` over ``requests`` synthetic requests, then prefill +
    cached decode of one prompt against ``model.forward``."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    eng = ServeEngine(cfg, batch=batch, max_seq=max_seq,
                      prefill_len=prefill_len, seed=seed)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=prefill_len,
                            mean_new=mean_new, seed=seed)
    reqs = [gen.request(i) for i in range(requests)]
    stats = eng.run(params, reqs)
    check(len(eng.done) == requests,
          f"{len(eng.done)} of {requests} requests finished")
    for r in reqs:
        # the prefill yields the first token, each decode one more
        check(r.output is not None and len(r.output) == r.max_new_tokens + 1,
              f"request {r.req_id}: {len(r.output or [])} tokens for "
              f"max_new_tokens {r.max_new_tokens}")
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, {stats['requests']} "
          f"requests, {stats['tokens']} tokens, {stats['iterations']} "
          "engine iterations", flush=True)

    # logits: prefill + cached decode vs one forward over the same tokens
    extra = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=check_tokens).astype(np.int32)
    toks = jnp.asarray(np.concatenate([reqs[0].prompt, extra]))[None]
    p = prefill_len
    want, _ = jax.jit(model.forward)(params, {"tokens": toks})
    want = np.asarray(want, np.float32)[0]
    cache = model.init_cache(1, max_seq)
    got, cache, _ = jax.jit(model.prefill)(params, {"tokens": toks[:, :p]},
                                           cache)
    errs = [rel_err(got[0, 0], want[p - 1])]
    decode = jax.jit(model.decode_step)
    for t in range(p, p + check_tokens):
        got, cache, _ = decode(params, toks[:, t:t + 1], cache)
        errs.append(rel_err(got[0, 0], want[t]))
    print(f"[serve] logits vs forward, max|err|/max|ref| per position: "
          f"{[f'{e:.3e}' for e in errs]} (tolerance {LOGIT_TOL})", flush=True)
    check(max(errs) <= LOGIT_TOL, f"logit error {max(errs):.3e} > {LOGIT_TOL}")
    return {"requests": stats["requests"], "tokens": stats["tokens"],
            "logit_err": max(errs)}


# -- chip configuration --------------------------------------------------
def chip_pipeline_config() -> PipelineConfig:
    return PipelineConfig(
        arch="qwen3-1.7b", reduce=False, n_layers=PIPELINE_LAYERS,
        platforms=("bf16", "bf16-chunk512"), selector="random",
        selector_args={"n_samples": 4, "seed": 0}, steps=24, seq_len=2048,
        batch=2, interval_steps=2.5, workers=0, max_attempts=1,
        stage_timeout_s=None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args(argv)

    try:
        device = phase_device()
    except RuntimeError as e:            # SmokeFailure, or no backend
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    print(f"[device] compilation cache: {use_compile_cache()}", flush=True)
    os.makedirs(args.out, exist_ok=True)
    clock = CompileClock()

    qwen3 = get_config("qwen3-1.7b")
    run_phase("kernels", clock, phase_kernels, qwen3,
              get_config("mamba2-780m"), seq=2048, decode_batch=4,
              decode_cache=4096)
    run_phase("pipeline", clock, phase_pipeline,
              chip_pipeline_config(),
              os.path.join(args.out, "store"))
    run_phase("serve", clock, phase_serve, qwen3, batch=4, max_seq=2048,
              prefill_len=512, requests=8, mean_new=24, check_tokens=8)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
