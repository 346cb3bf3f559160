"""Window kind ``serve_spans``: the ``serve`` window kind
(``bench/kinds/serve.py``, used as it is) with the program's engine spans
kept for the per-layer metrics of ``bench/spans.py``.

With ``--trace 0`` the run is ``serve``'s and ``repro.obs`` tracing stays
off.  With ``--trace 1`` tracing is on through the window, so the engine's
phase spans land in the profiler trace too, and the spans that ended before
the profiler started go into ``record["spans"]`` as ``(name, start s,
seconds, attrs)``, seconds from the window's start.

Either way, for an expert model the engine's ``serve.*`` counters of the
window go into the result's ``notes``: the mean ``held_tokens`` (token-expert
pairs routed to the experts held here) per decode and per prefill, the mean
``expert_load_max`` (the busiest held expert's pairs) over the mean held
expert's, per decode and per prefill, and the window's ``dropped_tokens``,
which a drop-free layer keeps at 0.
"""
from __future__ import annotations

from typing import Dict

from bench.kinds import serve

COUNTERS = ("serve.decode_iters", "serve.prefill_iters",
            "serve.held_tokens.decode", "serve.held_tokens.prefill",
            "serve.expert_load_max.decode", "serve.expert_load_max.prefill",
            "serve.dropped_tokens")


def _counts() -> Dict[str, float]:
    from repro import obs
    return {k: obs.metrics().value(k) or 0.0 for k in COUNTERS}


def window_spans(events, until_s: float):
    """``repro.obs`` span events that ended by ``until_s`` (seconds from
    the tracer's start, which is the window's) as ``(name, start s,
    seconds, attrs)``."""
    return [(e["name"], e["ts"] / 1e6, e["dur"] / 1e6, e["args"])
            for e in events
            if e["ph"] == "X" and (e["ts"] + e["dur"]) / 1e6 <= until_s]


def expert_notes(c: Dict[str, float], n_held: int) -> Dict[str, float]:
    """The notes of a window's counter increments ``c``; none for a model
    without experts."""
    if not (c["serve.held_tokens.decode"] or c["serve.held_tokens.prefill"]):
        return {}
    notes = {"dropped_tokens": int(c["serve.dropped_tokens"])}
    for kind in ("decode", "prefill"):
        n, held = c[f"serve.{kind}_iters"], c[f"serve.held_tokens.{kind}"]
        if n and held:
            notes[f"held_tokens_per_{kind}"] = held / n
            notes[f"expert_load_max_over_mean_{kind}"] = (
                c[f"serve.expert_load_max.{kind}"] * n_held / held)
    return notes


def run(cell, seed: int, seconds: float, trace: bool) -> Dict:
    from repro import obs
    window = serve.serve
    counted: Dict[str, float] = {}

    def spans_window(eng, params, served, seconds, trace_from=None,
                     record=None):
        before = _counts()
        tracer = obs.configure(trace=True) if trace_from is not None else None
        try:
            wall = window(eng, params, served, seconds,
                          trace_from=trace_from, record=record)
        finally:
            if tracer is not None:
                obs.configure(trace=False)
        if tracer is not None:
            record["spans"] = window_spans(tracer.events(), trace_from)
        after = _counts()
        counted.update({k: after[k] - before[k] for k in COUNTERS})
        return wall

    # serve.run calls its module's ``serve`` for the window
    serve.serve = spans_window
    try:
        out = serve.run(cell, seed, seconds, trace)
    finally:
        serve.serve = window
    moe = cell.program_config().moe
    out["notes"].update(expert_notes(counted, moe.n_local if moe else 1))
    return out
