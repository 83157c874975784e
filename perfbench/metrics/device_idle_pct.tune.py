"""Share of the traced window in which no operation ran on the device, in the
tune cells: 100 * (1 - busy / window), busy the union of the device's
operation intervals (``lib/trace_reduce.py``)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
