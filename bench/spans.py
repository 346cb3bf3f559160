"""The serving engine's iterations, read from the ``repro.obs`` spans a
traced run of the ``serve`` window kind keeps in ``record["spans"]``: tuples
``(name, start s, seconds, attrs)`` from the part of the window the
profiler did not slow.

Each ``serve.step`` span is one engine iteration, with its ``kind``
(``insert`` or ``decode``); the spans that start inside it are its phases.
The ``serve.read_*`` phases are the host's blocking reads of device results
(they never nest in one another); the rest of an iteration is host work
while the device runs or waits.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

STEP = "serve.step"
READ = "serve.read_"

Phase = Tuple[str, float, Dict]       # name, seconds, attrs


def iterations(record: Dict, kind: str) -> List[Tuple[float, List[Phase]]]:
    """``(seconds, phases)`` of each ``serve.step`` of ``kind``."""
    spans = sorted(record.get("spans") or [], key=lambda s: s[1])
    steps = [s for s in spans if s[0] == STEP]
    starts = [s[1] for s in steps]
    phases: List[List[Phase]] = [[] for _ in steps]
    for name, start, dur, attrs in spans:
        i = bisect.bisect_right(starts, start) - 1
        if name != STEP and i >= 0 and start <= starts[i] + steps[i][2]:
            phases[i].append((name, dur, attrs))
    return [(s[2], p) for s, p in zip(steps, phases)
            if s[3].get("kind") == kind]


def _mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def _read_s(phases: List[Phase]) -> float:
    return sum(d for name, d, _ in phases if name.startswith(READ))


def host_ms(record: Dict, kind: str) -> Optional[float]:
    """Mean over ``kind`` iterations of the step less its reads, in ms."""
    m = _mean([d - _read_s(p) for d, p in iterations(record, kind)])
    return None if m is None else m * 1e3


def read_ms(record: Dict, kind: str) -> Optional[float]:
    """Mean over ``kind`` iterations of the summed reads, in ms."""
    m = _mean([_read_s(p) for _, p in iterations(record, kind)])
    return None if m is None else m * 1e3


def reads(record: Dict, kind: str) -> Optional[float]:
    """Mean number of reads per ``kind`` iteration."""
    return _mean([sum(1 for name, _, _ in p if name.startswith(READ))
                  for _, p in iterations(record, kind)])


def attrs(record: Dict, name: str, key: str) -> List:
    """Attribute ``key`` of every span called ``name``."""
    return [a[key] for n, _, _, a in record.get("spans") or []
            if n == name and key in a]
