"""Backend compiles (and compile-cache loads) inside the window, counted
by a ``jax.monitoring`` listener: a read shape that set-up missed."""


def read(ctx):
    return float(ctx["window_compiles"])
