"""Programs built inside the measured window: JAX's backend-compile events
(``/jax/core/compile/backend_compile_duration``, a compile or a read from
the persistent cache) counted between the first request's due time and the
last answer.  Warm-up is meant to leave none."""


def read(ctx):
    return ctx.compiles_in_window
