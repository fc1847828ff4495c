"""Set-up: programs compiled or loaded from the compile cache inside the
measured window (``jax.monitoring`` backend-compile events)."""


def read(ctx):
    return ctx.compiles
