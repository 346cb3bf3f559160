"""Share of the roofline reached by the KDA layers of a decode step: the
least time their state, convolution state and weights take to read and
write at the chip's peak bandwidth, over the device time of the decode
program's ``nugget_block_kda`` operations per step of the traced window
(``bench/kda_roofline.py``; device trace)."""
from bench import kda_roofline


def read(record):
    return kda_roofline.share_pct(record.get("kda"), "decode")
