"""Share of the traced serving window in which no operation ran on the
device (profiler trace; see ``trace_reduce``)."""


def read(record):
    t = record.get("traces", {}).get("serve")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
