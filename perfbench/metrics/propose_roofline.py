"""The propose program's share of its roofline, in percent: the least time
one call needs on this chip (``lib/counts.py``: its bytes over the HBM
bandwidth, which bound it) over the device busy time per call."""


def read(ctx):
    tr, least = ctx.get("trace"), ctx.get("least")
    if not tr or not least or not ctx.get("steps") or tr["busy_s"] <= 0:
        return None
    return 100.0 * least["seconds"] / (tr["busy_s"] / ctx["steps"])
