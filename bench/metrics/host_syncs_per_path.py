"""Blocking device-to-host reads per path: the ``read`` spans that the
program wrote to the profiler trace (``repro.obs.trace``: one span around
each read the solve loop branches on or stores), per path.

The traced cycle is the run's one profiler session, so the program's
count of the spans it wrote to the profiler is the window's.  Read only
where the program wrote one ``path`` span per path of the window; a
program without the profiler output counts none and reads nothing."""


def read(ctx):
    if not ctx["trace_complete"]:      # cut short: nothing to read
        return None
    from repro.obs import trace

    counts = getattr(trace.TRACER, "profiler_counts", dict)()
    paths = ctx["counters"]["paths"]
    if not paths or counts.get("path") != paths:
        return None
    return counts.get("read", 0) / paths
