"""Seconds JAX spent tracing, lowering and compiling (or loading from its
persistent cache) during set-up, summed over threads (jax.monitoring)."""


def read(ctx):
    return ctx.setup_compile_s
