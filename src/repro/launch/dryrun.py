import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/init: jax locks the device count on first use.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct inputs (no allocation) on the production meshes, and record
memory/cost/collective analyses for the roofline (EXPERIMENTS.md §Dry-run).

A CPU-only rehearsal tool: importing this module sets ``XLA_FLAGS`` to 512
host devices, and ``--all`` starts one child process per cell after the
parent has configured JAX.  Never point it at a TPU, where one process owns
the chip; ``chip_smoke.py`` is the on-chip check.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all          # every cell, subprocess-per-cell
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import numpy as np


# activation-memory-driven gradient-accumulation factors (global batch 256)
MICROBATCH = {
    "mistral-large-123b": 64,
    "internvl2-76b": 64,
    "llama4-scout-17b-a16e": 16,
    "qwen2.5-14b": 16,
    "gemma3-4b": 8,
    "qwen3-1.7b": 4,
    "mamba2-780m": 8,
    "zamba2-1.2b": 8,
    "olmoe-1b-7b": 4,
    "whisper-tiny": 1,
}

V5E = {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9, "hbm_gb": 16}


def cell_id(arch: str, shape: str, mesh: str) -> str:
    return f"{arch}__{shape}__{mesh}"


def _tree_bytes_per_device(struct_tree, shardings) -> int:
    import jax
    total = 0.0
    for s, sh in zip(jax.tree.leaves(struct_tree),
                     jax.tree.leaves(shardings,
                                     is_leaf=lambda x: hasattr(x, "spec"))):
        shape = sh.shard_shape(s.shape)
        itemsize = 0.5 if "int4" in str(s.dtype) else s.dtype.itemsize
        total += float(np.prod(shape)) * itemsize
    return int(total)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             *, instrument: bool = True, causal_skip: bool = False,
             remat: Optional[str] = None,
             attn_chunk: Optional[int] = None,
             parallel_block: bool = False,
             remat_group: int = 1,
             weight_quant: str = "none",
             cache_quant: str = "none",
             capacity_factor: Optional[float] = None,
             microbatch_override: Optional[int] = None,
             extra_tag: str = "") -> Dict[str, Any]:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.core.blocks_lm import build_block_table
    from repro.distributed.sharding import (params_shardings, plan_for,
                                            use_rules)
    from repro.launch.mesh import make_production_mesh
    from repro.models import kvcache as KC
    from repro.models.model_zoo import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedule import constant
    from repro.train.state import init_train_state, make_train_step

    t_start = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))

    if shape_name == "long_500k" and not cfg.is_subquadratic:
        return {"cell": cell_id(arch, shape_name, mesh_kind),
                "status": "skipped(full-attention)",
                "note": "long_500k requires sub-quadratic attention "
                        "(DESIGN.md §Arch-applicability)"}

    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if attn_chunk:
        cfg = dataclasses.replace(cfg, attn_chunk=attn_chunk)
    if causal_skip:
        cfg = dataclasses.replace(cfg, attn_causal_skip=True)
    if parallel_block:
        cfg = dataclasses.replace(cfg, parallel_block=True)
    if remat_group > 1:
        cfg = dataclasses.replace(cfg, remat_group=remat_group)
    if weight_quant != "none":
        cfg = dataclasses.replace(cfg, weight_quant=weight_quant)
    if cache_quant != "none":
        cfg = dataclasses.replace(cfg, cache_quant=cache_quant)
    if capacity_factor and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor))

    mode = "train" if shape.kind == "train" else "serve"
    bytes_per_param = {"int8": 1.0, "int4": 0.5}.get(cfg.weight_quant, 2.0)
    # plan_for decides serve-FSDP from bf16 bytes; feed it the effective
    # byte count so quantized weights can stay TP-only (no per-token
    # weight gathers)
    plan = plan_for(mesh, arch, mode, shape_name,
                    int(cfg.param_count() * bytes_per_param / 2))
    model = build_model(cfg, plan)

    dp = int(np.prod([mesh.shape[a] for a in plan.dp_axes])) if plan.dp_axes else 1
    # effective devices doing distinct compute (roofline denominator):
    # whisper replicates over "model"; mamba2 long-context leaves "data" idle
    eff = dp * plan.tp_size
    if shape_name == "long_500k":
        data_sz = int(mesh.shape.get("data", 1))
        eff = plan.tp_size * (data_sz if cfg.family != "ssm" else 1)
    result: Dict[str, Any] = {
        "cell": cell_id(arch, shape_name, mesh_kind) + extra_tag,
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": n_dev, "kind": shape.kind,
        "tp": plan.tp_size,
        "dp": dp,
        "eff_devices": eff,
        "fsdp": plan.lookup("embed") is not None,
        "family": cfg.family,
        "n_layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "tokens": shape.tokens,
        "weight_quant": cfg.weight_quant,
        "cache_quant": cfg.cache_quant,
        "parallel_block": cfg.parallel_block,
        "remat_group": cfg.remat_group,
        "tp_ar_per_layer": 1 if cfg.parallel_block else 2,
        "grad_rs_bytes": 2.0 if cfg.param_dtype == "bfloat16" else 4.0,
        "bytes_per_param": bytes_per_param,
        "status": "running",
    }

    with mesh, use_rules(plan):
        if shape.kind == "train":
            mb = MICROBATCH.get(arch, 1)
            if multi_pod:
                mb = max(1, mb // 2)
            if cfg.remat_group > 1:
                mb = max(1, mb // cfg.remat_group)
            if microbatch_override:
                mb = microbatch_override
            result["microbatch"] = mb
            table = (build_block_table(model, shape) if instrument else None)
            opt_cfg = AdamWConfig()
            step_fn = make_train_step(model, opt_cfg, constant(1e-4),
                                      table=table, microbatch=mb,
                                      instrument=instrument)
            state_struct = jax.eval_shape(
                lambda: init_train_state(model, jax.random.PRNGKey(0),
                                         opt_cfg, table))
            p_axes = model.axes()
            p_shard = params_shardings(mesh, plan, p_axes)
            rep = NamedSharding(mesh, P())
            from repro.optim.adamw import OptState
            opt_shard = OptState(rep, p_shard, p_shard, p_shard)
            meter_shard = (jax.tree.map(lambda _: rep, state_struct.meter)
                           if state_struct.meter is not None else None)
            from repro.train.state import TrainState
            state_shard = TrainState(rep, p_shard, opt_shard, rep, meter_shard)
            batch_struct = model.input_specs(shape)
            bspec = {
                "tokens": NamedSharding(mesh, plan.spec(("batch", "seq"))),
                "labels": NamedSharding(mesh, plan.spec(("batch", "seq"))),
            }
            if "frames" in batch_struct:
                bspec["frames"] = NamedSharding(
                    mesh, plan.spec(("batch", None, None)))
            if "patches" in batch_struct:
                bspec["patches"] = NamedSharding(
                    mesh, plan.spec(("batch", None, None)))
            jfn = jax.jit(step_fn, in_shardings=(state_shard, bspec),
                          donate_argnums=(0,))
            lowered = jfn.lower(state_struct, batch_struct)
            state_bytes = _tree_bytes_per_device(state_struct, state_shard)
            result["state_bytes_per_device"] = state_bytes
            from repro.core.unit_of_work import trace_cost
            tc = trace_cost(step_fn, state_struct, batch_struct)
            result["trace_flops_global"] = tc.flops
            result["trace_bytes_global"] = tc.bytes
            result["trace_ops_global"] = tc.ops

        else:
            params_struct = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            p_shard = params_shardings(mesh, plan, model.axes())
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            c_spec = KC.cache_specs(cache_struct, plan)
            c_shard = jax.tree.map(
                lambda spec: NamedSharding(mesh, spec), c_spec,
                is_leaf=lambda x: isinstance(x, P))
            if shape.kind == "prefill":
                batch_struct = model.input_specs(shape)
                bspec = {"tokens": NamedSharding(mesh, plan.spec(("batch", "seq")))}
                if "frames" in batch_struct:
                    bspec["frames"] = NamedSharding(
                        mesh, plan.spec(("batch", None, None)))
                if "patches" in batch_struct:
                    bspec["patches"] = NamedSharding(
                        mesh, plan.spec(("batch", None, None)))
                jfn = jax.jit(model.prefill,
                              in_shardings=(p_shard, bspec, c_shard),
                              donate_argnums=(2,))
                lowered = jfn.lower(params_struct, batch_struct, cache_struct)
            else:
                tok_struct = model.input_specs(shape)["token"]
                tspec = NamedSharding(mesh, plan.spec(("batch", None)))
                jfn = jax.jit(model.decode_step,
                              in_shardings=(p_shard, tspec, c_shard),
                              donate_argnums=(2,))
                lowered = jfn.lower(params_struct, tok_struct, cache_struct)
            result["params_bytes_per_device"] = _tree_bytes_per_device(
                params_struct, p_shard)
            result["cache_bytes_per_device"] = _tree_bytes_per_device(
                cache_struct, c_shard)
            from repro.core.unit_of_work import trace_cost
            if shape.kind == "prefill":
                tc = trace_cost(model.prefill, params_struct, batch_struct,
                                cache_struct)
            else:
                tc = trace_cost(model.decode_step, params_struct, tok_struct,
                                cache_struct)
            result["trace_flops_global"] = tc.flops
            result["trace_bytes_global"] = tc.bytes
            result["trace_ops_global"] = tc.ops

        result["lower_s"] = time.time() - t_start
        t_c = time.time()
        compiled = lowered.compile()
        result["compile_s"] = time.time() - t_c

        ca = compiled.cost_analysis() or {}
        result["cost_analysis"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds",
             "bytes accessed output", "utilization operand 0 {}")}
        result["flops"] = float(ca.get("flops", 0.0))
        result["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))

        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                             "temp_size_in_bytes", "generated_code_size_in_bytes",
                             "alias_size_in_bytes"):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        result[f"mem_{attr}"] = int(v)
        except Exception as e:                        # pragma: no cover
            result["memory_analysis_error"] = str(e)

        from repro.core.hlo_analysis import collective_stats, op_histogram
        hlo = compiled.as_text()
        result["hlo_bytes"] = len(hlo)
        result["collectives"] = collective_stats(hlo)
        result["collective_bytes"] = sum(
            v["bytes"] for v in result["collectives"].values())
        hist = op_histogram(hlo)
        result["op_histogram_top"] = dict(
            sorted(hist.items(), key=lambda kv: -kv[1])[:20])

    result["status"] = "ok"
    result["total_s"] = time.time() - t_start
    return result


# ---------------------------------------------------------------------------


def all_cells():
    from repro.configs import SHAPES, get_config, list_archs
    for arch in list_archs():
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                yield arch, shape, mesh


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-instrument", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--remat")
    ap.add_argument("--attn-chunk", type=int)
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--parallel-block", action="store_true")
    ap.add_argument("--remat-group", type=int, default=1)
    ap.add_argument("--weight-quant", default="none")
    ap.add_argument("--cache-quant", default="none")
    ap.add_argument("--capacity-factor", type=float)
    ap.add_argument("--microbatch", type=int)
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.all:
        failures = []
        for arch, shape, mesh in all_cells():
            path = os.path.join(args.out, cell_id(arch, shape, mesh) + ".json")
            if args.skip_existing and os.path.exists(path):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--out", args.out]
            if args.no_instrument:
                cmd.append("--no-instrument")
            print(f"=== {cell_id(arch, shape, mesh)}", flush=True)
            rc = subprocess.call(cmd)
            if rc != 0:
                failures.append(cell_id(arch, shape, mesh))
        print("failures:", failures)
        return 1 if failures else 0

    assert args.arch and args.shape
    path = os.path.join(args.out,
                        cell_id(args.arch, args.shape, args.mesh)
                        + args.tag + ".json")
    try:
        res = run_cell(args.arch, args.shape, args.mesh,
                       instrument=not args.no_instrument,
                       remat=args.remat, attn_chunk=args.attn_chunk,
                       causal_skip=args.causal_skip,
                       parallel_block=args.parallel_block,
                       remat_group=args.remat_group,
                       weight_quant=args.weight_quant,
                       cache_quant=args.cache_quant,
                       capacity_factor=args.capacity_factor,
                       microbatch_override=args.microbatch,
                       extra_tag=args.tag)
    except Exception:
        res = {"cell": cell_id(args.arch, args.shape, args.mesh) + args.tag,
               "status": "error", "traceback": traceback.format_exc()}
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    ok = res["status"].startswith(("ok", "skipped"))
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("op_histogram_top", "traceback")}, indent=1))
    if not ok:
        print(res.get("traceback", ""), file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
