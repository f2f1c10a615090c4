"""Backend compile seconds during set-up, persistent-cache reads included,
summed from JAX's ``/jax/core/compile/backend_compile_duration`` events."""


def read(ctx):
    return ctx.get("compile_s")
