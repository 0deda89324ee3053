"""Compile requests (``jax.monitoring``) between the window's two clocks."""


def read(run):
    return run["compiles_in_window"]
