"""XLA compiles (jax.monitoring events) inside the measured window."""


def read(ctx):
    return float(ctx["compiles_in_window"])
