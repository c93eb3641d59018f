"""Seconds from the start of the process to the first timed request (host
clock): JAX start-up, data generation, warm-up and compiles."""


def read(ctx):
    return ctx.setup_s
