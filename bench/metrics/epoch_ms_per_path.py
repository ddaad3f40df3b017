"""Device time of the epoch blocks (``core/solver._inner_rounds``,
``bcd_epochs``), per path."""

PROGRAMS = r"^jit_(_inner_rounds|bcd_epochs)$"


def read(ctx):
    if not ctx["trace_complete"]:      # cut short: nothing to read
        return None
    runs, seconds = ctx["trace"].device_seconds(PROGRAMS)
    if not runs:
        return None
    return 1e3 * seconds / ctx["counters"]["paths"]
