"""Programs built inside the measured window, compiled or loaded from
the persistent cache, counted by a jax.monitoring listener."""
def read(ctx):
    return float(ctx.compiles)
