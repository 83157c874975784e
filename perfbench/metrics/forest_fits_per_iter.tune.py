"""Forest fits per tuner iteration: the program's ``forest_fit`` spans
(``repro.obs``, one per ``ProbabilisticRandomForest.fit``) in the traced
window over the iterations completed. None where the program emits no such
span, as before it had one."""

NAME = "forest_fit"


def read(ctx):
    spans, n = ctx.get("spans"), ctx.get("steps")
    if spans is None or not n:
        return None
    fits = sum(1 for name, _ in spans if name == NAME)
    if not fits:
        return None
    return fits / n
