"""Mean host time of an engine iteration that inserts a request: its
prefill, the copy into a decode slot and the first token (host clock,
untraced part of the window)."""
import numpy as np


def read(record):
    d = record.get("prefill_s")
    return float(np.mean(d)) * 1e3 if d else None
