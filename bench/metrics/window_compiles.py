"""Programs compiled, or loaded from the persistent cache, inside the
measured window: ``jax.monitoring`` backend-compile events between the
first study's start and the last study's end.  A warmed cell reads 0.
"""


def read(ctx):
    return ctx["window_compiles"]
