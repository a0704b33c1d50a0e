"""JAX backend compilations (cache loads included) during the window."""


def read(ctx):
    return float(ctx["compiles"])
