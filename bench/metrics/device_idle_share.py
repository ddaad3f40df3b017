"""Share of the traced window in which the device ran nothing: 1 - (union
of the device's busy intervals) / window, from the trace (``bench/trace.py``
says which events are busy)."""


def read(ctx):
    if not ctx["trace_complete"]:      # cut short: nothing to read
        return None
    s = ctx["trace"]
    return 1.0 - s.busy_s / s.window_s
