"""Share of the roofline reached by the KDA layers of a prefill: the larger
of their operations at peak FLOP/s and their bytes at peak bandwidth, over
the device time of the prefill program's ``nugget_block_kda`` operations
per prefill of the traced window (``bench/kda_roofline.py``; device
trace)."""
from bench import kda_roofline


def read(record):
    return kda_roofline.share_pct(record.get("kda"), "prefill")
