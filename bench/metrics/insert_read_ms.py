"""Mean time an insert iteration waits in blocking reads of device results:
its summed ``serve.read_*`` spans (program spans, host clock, untraced part
of the window)."""
from bench import spans


def read(record):
    return spans.read_ms(record, "insert")
