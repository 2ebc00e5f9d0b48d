"""Executables compiled inside the window (JAX's backend builds less its
persistent-cache hits), counted by ``jax.monitoring``."""


def read(ctx):
    if ctx.get("kind") != "sim":
        return None
    return ctx["window_compiles"]["compiles"]
