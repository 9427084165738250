"""XLA backend compiles that ``jax.monitoring`` reported inside the
window."""


def read(ctx):
    return float(ctx["compiles_in_window"])
