"""Set-up seconds spent compiling or loading compiled programs from the
persistent cache: jax's ``/jax/core/compile/backend_compile_duration``
events (``jax.monitoring``) before the window opens."""


def read(ctx):
    return ctx.setup_compile_s if ctx.setup_compile_s > 0 else None
