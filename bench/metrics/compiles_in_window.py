"""Programs JAX compiled for the backend inside the window (counted from
``jax.monitoring`` backend-compile events)."""


def read(w):
    return float(w.compiles)
