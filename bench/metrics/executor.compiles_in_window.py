"""Compiles and compile-cache loads between the window's start and end,
counted from ``jax.monitoring`` events. Set-up should leave none. Layer:
cluster (runner, executor)."""
UNIT = "count"


def read(ctx):
    return ctx.compiles_in_window
