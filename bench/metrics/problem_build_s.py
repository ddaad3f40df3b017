"""Seconds the driver's one set-up build of the problem took: the
program's REGISTRY gauge ``sgl.make_problem_s``, which ``make_problem``
sets from its entry until its outputs are ready on the device.  Nothing
else in a run builds a problem, so the gauge holds that build.  A program
without the gauge, or a run that never set it, reads nothing."""


def read(ctx):
    from repro.obs import metrics

    try:
        gauge = metrics.REGISTRY.get("sgl.make_problem_s")
    except KeyError:
        return None
    return gauge.value or None
