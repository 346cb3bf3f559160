"""Mean host time of an engine iteration that decodes every slot, tokens
read back to the host included (host clock, untraced part of the
window)."""
import numpy as np


def read(record):
    d = record.get("decode_s")
    return float(np.mean(d)) * 1e3 if d else None
