"""Device time of the certified rounds, full and compact
(``core/solver._screen_round``, ``_screen_round_compact``), per path."""

PROGRAMS = r"^jit__screen_round(_compact)?$"


def read(ctx):
    if not ctx["trace_complete"]:      # cut short: nothing to read
        return None
    runs, seconds = ctx["trace"].device_seconds(PROGRAMS)
    if not runs:
        return None
    return 1e3 * seconds / ctx["counters"]["paths"]
