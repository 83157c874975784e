"""Milliseconds per tuner iteration spent in the program's ``workload_eval``
spans (``repro.obs``), over the traced window: their summed durations over
the iterations completed. Spans of one name do not nest in one another."""

NAME = "workload_eval"


def read(ctx):
    spans, n = ctx.get("spans"), ctx.get("steps")
    if spans is None or not n:
        return None
    return 1000.0 * sum(d for name, d in spans if name == NAME) / n
