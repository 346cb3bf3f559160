"""The KDA layers' roofline in a traced serving window: what one step's KDA
work needs at the chip's peaks, and the device time its operations took.

Operations and bytes are counted from the configuration's shapes
(``decode_bytes``, ``prefill_flops``, ``prefill_bytes``): the least the
algorithm needs, so that a share of the roofline cannot pass 100% unless the
trace leaves work out.  A decode step reads and writes every slot's state
(heads x D x D float32) and convolution state (width - 1 positions of q, k
and v, bf16) once and reads the KDA weights once; its bound is the bytes.
A prefill runs the projections over the prompt and the chunked recurrence's
matrix products (``CHUNK`` positions a chunk, triangular halves counted
once); its bound is the larger of operations and bytes.

Device time comes from the profiler trace (``read_xplane`` keeps, per TPU
plane, the ``XLA Modules`` line, one event per program run, and the ``XLA
Ops`` line, one event per operation with the name of its HLO instruction).
An operation is a KDA operation when its instruction carries the
``nugget_block_kda`` scope in the compiled program's metadata
(``repro.core.hlo_analysis.find_scope_labels``); prefill and decode are
different programs whose instruction names collide, so an operation is put
down to the program run whose ``XLA Modules`` event holds its start.  The
device time of a kind of step is the union of its KDA operations'
intervals over the traced window, divided by the runs of its program that
started in the window.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from repro.models.kda import CHUNK

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.traced"
SCOPE = "nugget_block_kda"
# the programs' HLO module names (``ServeEngine``'s jitted steps)
PROGRAMS = {"decode": "decode_step", "prefill": "prefill_row"}

# Published peaks (Google Cloud documentation, "TPU v5e"), by device_kind
PEAKS = {"TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9}}

Event = Tuple[str, int, int]    # name, start_ns, duration_ns


# -- counts ------------------------------------------------------------------
def _dims(conf: Dict):
    lin = conf["linear_attn_config"]
    n = conf["num_hidden_layers"]
    n_kda = sum(1 for i in lin["kda_layers"] if i <= n)
    h, d = lin["num_heads"], lin["head_dim"]
    return n_kda, h, d, lin["short_conv_kernel_size"], conf["hidden_size"]


def weight_count(conf: Dict) -> int:
    """Parameters of one KDA layer (projections, convolutions, gates with
    their low rank of the head dimension, norms)."""
    _, h, d, kw, dm = _dims(conf)
    hd = h * d
    return (3 * dm * hd + hd * dm          # q, k, v, o
            + 3 * kw * hd                  # convolutions
            + dm * h                       # beta
            + 2 * (dm * d + d * hd)        # decay and output gate
            + hd + hd + h + d)             # gate bias, dt_bias, A_log, norm


def decode_bytes(conf: Dict, slots: int) -> float:
    """Bytes a decode step of ``slots`` rows moves at least through the KDA
    layers: each state and convolution state read and written, the
    weights read (bf16)."""
    n_kda, h, d, kw, _ = _dims(conf)
    state = slots * h * d * d * 4
    conv = slots * (kw - 1) * 3 * h * d * 2
    return n_kda * (2 * state + 2 * conv + 2 * weight_count(conf))


def prefill_flops(conf: Dict, tokens: int) -> float:
    """Operations of a prefill of ``tokens`` positions through the KDA
    layers: the projections and the chunked recurrence's products."""
    n_kda, h, d, kw, dm = _dims(conf)
    hd = h * d
    proj = 2 * tokens * (4 * dm * hd + dm * h + 2 * (dm * d + d * hd))
    conv = 2 * tokens * kw * 3 * hd
    chunks = -(-tokens // CHUNK)
    c = CHUNK
    # per chunk and head: the two triangular pair products (keys with keys,
    # queries with keys), the triangular solve's right-hand sides, the
    # queries' part of the intra-chunk output, and the three products with
    # the carried state
    per = 2 * c * c * d + 2 * c * c * d + c * c * d + 6 * c * d * d
    return n_kda * (proj + conv + chunks * h * per)


def prefill_bytes(conf: Dict, tokens: int) -> float:
    """Bytes a prefill moves at least through the KDA layers: the weights
    read, the layers' input read and output written (bf16), and the final
    states written."""
    n_kda, h, d, kw, dm = _dims(conf)
    return n_kda * (2 * weight_count(conf) + 2 * 2 * tokens * dm
                    + h * d * d * 4 + (kw - 1) * 3 * h * d * 2)


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    peak = PEAKS[device_kind]          # a device not in the table is an error
    return max(flops / peak["flops"], nbytes / peak["bytes_per_s"])


# -- the trace ---------------------------------------------------------------
def read_xplane(path: str) -> Dict:
    """Per TPU plane its program runs (``modules``, from the ``XLA
    Modules`` line) and operations (``ops``) as ``(name, start_ns,
    duration_ns)``, each operation with the module its event stats name
    (empty where they name none); the host window span ``bench.traced``
    and the starts of the engine's ``serve.decode`` / ``serve.prefill``
    spans; and what the trace offers: the TPU lines' names, the stats of
    an operation's event, and whether any names the KDA scope."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List]] = {}
    window: List[Event] = []
    steps: Dict[str, List[int]] = {k: [] for k in PROGRAMS}
    lines, op_stats, scope_in_stats = set(), [], False
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window.append((e.name, int(e.start_ns),
                                       int(e.duration_ns)))
                    elif e.name.startswith("serve."):
                        kind = e.name[len("serve."):]
                        if kind in steps:
                            steps[kind].append(int(e.start_ns))
            continue
        p = planes.setdefault(plane.name, {"modules": [], "ops": []})
        for line in plane.lines:
            lines.add(line.name)
            if line.name == MODULES_LINE:
                p["modules"] += [(e.name, int(e.start_ns),
                                  int(e.duration_ns)) for e in line.events]
            elif line.name == OPS_LINE:
                for e in line.events:
                    stats = _stats(e)
                    if not op_stats:
                        op_stats = sorted(k for k, _ in stats)
                    scope_in_stats = scope_in_stats or any(
                        SCOPE in str(v) for _, v in stats)
                    module = next((str(v) for k, v in stats
                                   if "module" in k), "")
                    p["ops"].append((e.name.split(" = ")[0],
                                     int(e.start_ns), int(e.duration_ns),
                                     module))
    return {"planes": planes, "window": window[:1], "steps": steps,
            "lines": sorted(lines), "op_stats": op_stats,
            "scope_in_stats": scope_in_stats}


def _stats(event) -> List:
    try:
        return list(event.stats)
    except Exception:                  # stats this build cannot decode
        return []


def _union_ns(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def program_time(trace: Dict, kind: str,
                 names: Optional[set]) -> Tuple[float, int]:
    """(seconds, runs) of the ``kind`` (``decode`` or ``prefill``) program
    over the traced window, averaged over the planes: the union of the
    intervals of its operations named in ``names`` (all of them if None),
    and its runs that started in the window.  An operation is the
    program's when the program run on the ``XLA Modules`` line holds its
    start; on a plane without that line, when its stats name the module,
    and where they name none it is counted for either program (so the
    time is never short).  Runs come from the ``XLA Modules`` line, or
    else from the engine's spans of that kind."""
    if not trace["window"] or not trace["planes"]:
        return 0.0, 0
    program = PROGRAMS[kind]
    _, w0, wd = trace["window"][0]
    w1 = w0 + wd
    secs, runs = 0.0, 0
    for p in trace["planes"].values():
        mods = sorted((s, s + d, name) for name, s, d in p["modules"])
        starts = [m[0] for m in mods]
        mine = [program in m[2] for m in mods]
        if mods:
            runs += sum(1 for (s, _, _), ok in zip(mods, mine)
                        if ok and w0 <= s < w1)
        else:
            runs += sum(1 for s in trace["steps"][kind] if w0 <= s < w1)
        spans = []
        for name, s, d, module in p["ops"]:
            if names is not None and name.lstrip("%") not in names:
                continue
            if mods:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or not mine[i] or s >= mods[i][1]:
                    continue
            elif module and program not in module:
                continue
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b))
        secs += _union_ns(spans) / 1e9
    n = len(trace["planes"])
    return secs / n, round(runs / n)


def record(trace: Dict, texts: Dict[str, str], conf: Dict, slots: int,
           prompt_len: int, device_kind: str) -> Dict:
    """The numbers the KDA metrics read, from a window's ``trace`` and the
    compiled programs' HLO ``texts`` (``decode``, ``prefill``): per kind of
    step its KDA operations' device seconds per run, its roofline seconds
    and the runs counted; and the KDA operations' share of the decode
    program's device time."""
    from repro.core.hlo_analysis import find_scope_labels
    out: Dict = {k: trace.get(k) for k in ("scope_in_stats", "lines",
                                           "op_stats")}
    need = {"decode": (0.0, decode_bytes(conf, slots)),
            "prefill": (prefill_flops(conf, prompt_len),
                        prefill_bytes(conf, prompt_len))}
    for kind, (flops, nbytes) in need.items():
        names = set(find_scope_labels(texts.get(kind, ""), SCOPE))
        kda_s, runs = program_time(trace, kind, names)
        if kind == "decode":
            all_s, _ = program_time(trace, kind, None)
            out["kda_share_of_decode_pct"] = (100.0 * kda_s / all_s
                                              if all_s else None)
        out[kind] = {"runs": runs, "kda_ops": len(names),
                     "device_s": kda_s / runs if runs else None}
        if runs:           # a TPU trace: its device must be in ``PEAKS``
            out[kind]["roofline_s"] = roofline_s(flops, nbytes, device_kind)
    return out


def share_pct(rec: Dict, kind: str) -> Optional[float]:
    """A metric's reading: roofline seconds over device seconds, in %."""
    k = (rec or {}).get(kind) or {}
    if not k.get("device_s"):
        return None
    return 100.0 * k["roofline_s"] / k["device_s"]
