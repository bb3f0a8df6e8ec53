"""Programs JAX compiled or loaded from its cache inside the window
(``jax.monitoring`` backend-compile events); a steady window reads 0."""


def read(run):
    return run.window_compiles
