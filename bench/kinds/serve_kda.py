"""Window kind ``serve_kda``: the ``serve_spans`` window kind
(``bench/kinds/serve_spans.py``, used as it is: the engine's spans kept in a
traced run, the router's counts in the notes) with a second number compared
for ``correct`` and, in a traced run, the device time of the KDA layers'
operations for ``bench/kda_roofline.py``.

The comparison (``checks``) is ``serve``'s widest gap, ``logit_gap``, and
``logit_gap_mean``, the mean gap over the same served tokens.  The widest
gap alone cannot tell this model's bf16 program from the reference computed
in fp8: a flip of a held expert at the router's eighth choice moves a few
tokens' logits about as far as fp8 rounding moves the widest one, since the
KDA layers have no softmax to magnify that rounding.  So the widest gap's
limit is a guard, about twice the program's widest reading, and the mean
parts the program from the control: it follows the rounding of every
token, and the two lie about 20 times apart (PERF.md section 2).

With ``--trace 1`` the first run of each of the engine's prefill and decode
programs also keeps that program's compiled HLO text (read from the
executable the run compiled, so nothing compiles twice), the profiler trace
of the window is read for its program runs and operations, and
``record["kda"]`` gets what ``kda_roofline.record`` makes of them: per kind
of step the KDA operations' device seconds per run and the roofline
seconds.  The notes add the KDA operations' share of the decode program's
device time and whether the trace's own event stats name the KDA scope.

Either way the notes give the decode's occupancy over the window: the mean
and the largest number of live slots a decode stepped (``live_slots_mean``,
``live_slots_max``) of the ``slots`` it steps, since every decode reads and
writes the KDA state of every slot, live or not.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from bench import kda_roofline, trace_reduce
from bench.kinds import serve, serve_spans


# ``serve``'s comparison, kept apart from the patched module attribute
_serve_checks = serve.checks


def checks(cell, gap: np.ndarray) -> Dict:
    """``serve.checks`` and the mean of ``gap``, each beside its limit."""
    out = _serve_checks(cell, gap)
    out["logit_gap_mean"] = {
        "value": float(gap.mean()) if gap.size else float("inf"),
        "limit": cell.spec["limits"]["logit_gap_mean"]}
    return out


def _keep_text(fn, name: str, texts: Dict[str, str]):
    """``fn`` (a jitted engine program) that keeps its compiled HLO text
    under ``name`` when first called."""
    def call(*args):
        if name not in texts:
            # lowered before the call, which may donate ``args``; the call
            # then finds the executable compiled here
            texts[name] = fn.lower(*args).compile().as_text()
        return fn(*args)
    return call


def _count_live(eng, live: List[Tuple[float, int]]):
    """``eng._decode`` that notes, at each call, the time and the number of
    live slots it steps."""
    fn = eng._decode

    def call(*args):
        live.append((time.perf_counter(), int(eng.active.sum())))
        return fn(*args)
    return call


def occupancy(live: List[Tuple[float, int]], since: float) -> Dict:
    """The notes of the decodes in ``live`` that started at ``since`` or
    later."""
    n = [k for t, k in live if t >= since]
    if not n:
        return {}
    return {"live_slots_mean": float(np.mean(n)), "live_slots_max": max(n),
            "decodes": len(n)}


def run(cell, seed: int, seconds: float, trace: bool) -> Dict:
    import jax
    texts: Dict[str, str] = {}
    seen: Dict = {}
    live: List[Tuple[float, int]] = []
    build, load = serve.build, trace_reduce.load

    def build_keeping(cell):
        eng, init = build(cell)
        if trace:
            eng._prefill = _keep_text(eng._prefill, "prefill", texts)
            eng._decode = _keep_text(eng._decode, "decode", texts)
        eng._decode = _count_live(eng, live)
        return eng, init

    def load_keeping(path):
        seen["trace"] = kda_roofline.read_xplane(path)
        return load(path)

    # serve.run calls its module's ``checks`` and ``build``, and the
    # traced window its ``trace_reduce.load``
    patches = [(serve, "checks", checks), (serve, "build", build_keeping)]
    if trace:
        patches.append((trace_reduce, "load", load_keeping))
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    for mod, name, fn in patches:
        setattr(mod, name, fn)
    try:
        out = serve_spans.run(cell, seed, seconds, trace)
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)
    out["notes"].update(occupancy(live, out["window_start"]))
    if "trace" in seen:
        rec = kda_roofline.record(
            seen["trace"], texts, cell.config, cell.spec["slots"],
            cell.traffic["prompt_len"], jax.devices()[0].device_kind)
        out["record"]["kda"] = rec
        out["notes"].update({
            "kda_share_of_decode_pct": rec["kda_share_of_decode_pct"],
            "kda_scope_in_trace_stats": rec["scope_in_stats"],
            "kda_runs": {k: rec[k]["runs"] for k in ("decode", "prefill")},
            "trace_device_lines": rec["lines"],
            "trace_op_stats": rec["op_stats"]})
    return out
