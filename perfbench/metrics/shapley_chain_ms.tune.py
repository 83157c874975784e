"""Milliseconds per tuner iteration spent in the program's ``shapley_chain``
spans (``repro.obs``: the bitvector chain walk of the Shapley attribution,
inside ``space_compression``), over the traced window: their summed
durations over the iterations completed. Spans of one name do not nest in
one another. None where the program emits no such span, as before it had
one."""

NAME = "shapley_chain"


def read(ctx):
    spans, n = ctx.get("spans"), ctx.get("steps")
    if spans is None or not n:
        return None
    durs = [d for name, d in spans if name == NAME]
    if not durs:
        return None
    return 1000.0 * sum(durs) / n
