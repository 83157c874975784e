"""Device busy time per propose call in the traced window, in milliseconds:
the union of the device's operation intervals over the calls completed."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps") or tr["busy_s"] <= 0:
        return None
    return 1000.0 * tr["busy_s"] / ctx["steps"]
