"""Programs compiled (or loaded from the persistent cache) inside the
measured window, counted through ``jax.monitoring``; should be 0."""


def read(run):
    return run.compiles_in_window
