"""Reduce a profiler trace to the device's busy and idle time, the device
operations that took most time, and the idle gaps by what the host was
doing in them.

``load`` reads a ``.xplane.pb`` (``jax.profiler.ProfileData``) into plain
lists; ``reduce`` works on those lists only, so a recorded trace in
``bench/testdata/`` reduces to fixed numbers on any machine.

- The window is the host span ``bench.traced`` (``common.traced``).
- Device time is read from each TPU plane's ``XLA Ops`` line, each
  operation named by its HLO instruction (``%fusion.12``): busy is the
  union of the operations' intervals inside the window, averaged over the
  chips in the trace.
- An idle gap is a stretch of the window in which no operation runs on a
  chip.  It is put down to the innermost ``bench.*`` host span that covers
  most of it (``idle`` where none does).
"""
from __future__ import annotations

import gzip
import json
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.traced"
TOP = 10

Event = Tuple[str, int, int]          # name, start_ns, duration_ns


def load(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (e.name.split(" = ")[0], int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        else:
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return {"device": device, "host": host}


def save(events: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(gap: Tuple[int, int], spans: List[Event]) -> str:
    a, b = gap
    best, best_cover, best_len = "idle", 0, None
    for name, s, d in spans:
        cover = min(b, s + d) - max(a, s)
        if cover <= 0:
            continue
        # more of the gap covered wins; on a tie the shorter (inner) span
        if cover > best_cover or (cover == best_cover and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def reduce(events: Dict) -> Dict:
    """``busy_s`` and ``window_s`` (seconds), ``device_ops`` and
    ``idle_gaps`` (lists of ``[name, seconds]``, longest first, at most
    ``TOP``), and ``chips``, the number of device planes read."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not windows or not events["device"]:
        return {}
    w0, w1 = windows[0]
    spans = [(n, s, d) for n, s, d in events["host"] if n != WINDOW]
    busy, ops = 0, defaultdict(int)
    gaps = defaultdict(int)
    for plane, evs in sorted(events["device"].items()):
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] += b - a
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_label((a, b), spans)] += b - a
    n = len(events["device"])

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:TOP]]

    return {"busy_s": busy / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "chips": n, "device_ops": top(ops), "idle_gaps": top(gaps)}
