"""Compiled-HLO analysis: op histograms, collective traffic, marker labels.

Consumers:
- the model-accuracy case study (paper §V-B: per-nugget compiled-op histogram
  vs portable-IR histogram localizes where the backend "microcodes"
  differently than the IR-level view assumes),
- zero-overhead marker location in "simulation" (named_scope labels survive
  into HLO metadata — the gem5 PC-label analogue).
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\]"
    r"(?:\{[^}]*\})?\s+([\w\-]+)\(")
_TUPLE_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\((.*?)\)\s+([\w\-]+)\(")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype, 4)
    if not dims.strip():
        return b
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * b


def parse_defs(hlo_text: str) -> Dict[str, int]:
    """var name -> result bytes, for every definition line."""
    sizes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, dtype, dims, _op = m.groups()
            sizes[name] = _shape_bytes(dtype, dims)
            continue
        m = _TUPLE_DEF_RE.match(line)
        if m:
            name, inner, _op = m.groups()
            total = 0
            for part in re.finditer(r"([a-z0-9]+)\[([\d,]*)\]", inner):
                total += _shape_bytes(part.group(1), part.group(2))
            sizes[name] = total
    return sizes


def op_histogram(hlo_text: str) -> Dict[str, int]:
    """Opcode -> count over all computations (incl. fusion bodies)."""
    hist: Dict[str, int] = collections.Counter()
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            hist[m.group(4)] += 1
            continue
        m = _TUPLE_DEF_RE.match(line)
        if m:
            hist[m.group(3)] += 1
    return dict(hist)


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: op count + operand bytes.

    Operand bytes are resolved through the def-site size map; if an operand
    is unknown (e.g. a parameter), the op's own result size is used as the
    fallback estimate.
    """
    sizes = parse_defs(hlo_text)
    stats: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "bytes": 0.0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line) or _TUPLE_DEF_RE.match(line)
        if not m:
            continue
        op = m.group(4) if m.re is _DEF_RE else m.group(3)
        base = None
        for kind in COLLECTIVES:
            if op == kind or op.startswith(kind + "-"):
                base = kind
                break
        if base is None:
            continue
        # operand list: names inside the call parens
        call = line[line.index(op + "(") + len(op) + 1:]
        depth, args = 1, []
        buf = ""
        for ch in call:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            if ch == "," and depth == 1:
                args.append(buf)
                buf = ""
            else:
                buf += ch
        if buf.strip():
            args.append(buf)
        total = 0
        for a in args:
            names = re.findall(r"%?([\w.\-]+)", a.strip())
            if names and names[-1] in sizes:
                total += sizes[names[-1]]
        if total == 0:
            name = m.group(1)
            total = sizes.get(name, 0)
        stats[base]["count"] += 1
        stats[base]["bytes"] += float(total)
    return stats


def find_scope_labels(hlo_text: str, needle: str) -> List[str]:
    """Locate ops whose metadata carries a named_scope label containing
    ``needle`` — zero-overhead marker tracking in the compiled program."""
    out = []
    for line in hlo_text.splitlines():
        if "metadata=" in line and needle in line:
            m = _DEF_RE.match(line) or _TUPLE_DEF_RE.match(line)
            if m:
                out.append(m.group(1))
    return out


def histogram_delta(a: Dict[str, int], b: Dict[str, int]
                    ) -> List[Tuple[str, int, int]]:
    """Sorted (op, count_a, count_b) where counts differ — the §V-B
    'microcoding' localization view."""
    keys = set(a) | set(b)
    rows = [(k, a.get(k, 0), b.get(k, 0)) for k in keys
            if a.get(k, 0) != b.get(k, 0)]
    return sorted(rows, key=lambda r: -abs(r[1] - r[2]))
