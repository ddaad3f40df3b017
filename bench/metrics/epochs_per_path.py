"""BCD epochs per path: ``PathResult.epochs`` summed over the traced
window's paths, per path (the solve's own counter)."""


def read(ctx):
    c = ctx["counters"]
    return c["epochs"] / c["paths"] if c["paths"] else None
