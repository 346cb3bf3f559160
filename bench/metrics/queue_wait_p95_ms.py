"""95th percentile of the time a request waits from its due time until the
engine starts its prefill (host clock, untraced part of the window)."""
import numpy as np


def read(record):
    w = record.get("queue_wait_s")
    return float(np.percentile(w, 95)) * 1e3 if w else None
