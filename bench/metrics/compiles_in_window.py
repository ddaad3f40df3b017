"""Programs compiled, or loaded from the persistent cache, inside the
window (``jax.monitoring`` backend-compile spans and cache hits).  Set-up
warms every shape, so this should read 0."""


def read(ctx):
    return ctx["compiles"]
