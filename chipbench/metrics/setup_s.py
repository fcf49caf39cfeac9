"""Seconds from the start of the process to the start of the window:
JAX and the device, warm-up, compilation or cache loads, daemon start,
cache fill."""


def read(ctx):
    return ctx.setup_s
