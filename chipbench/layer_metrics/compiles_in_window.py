"""Compile: backend compile requests (``jax.monitoring``) between the end
of the warm-up and the end of the window; has to read 0."""


def read(ctx):
    return ctx.compiles_in_window
