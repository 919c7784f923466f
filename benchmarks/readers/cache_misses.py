"""jax's persistent-compilation-cache misses of this run's process."""


def read(ctx, params):
    return float(ctx["cache"]["misses"])
