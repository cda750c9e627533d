"""window_compiles: programs the process had to produce inside the measured
window, backend compiles plus loads from the persistent cache, counted by
``jax.monitoring``.  A warm-up that covered every shape leaves it at 0."""


def read(ctx):
    return ctx.window_programs
