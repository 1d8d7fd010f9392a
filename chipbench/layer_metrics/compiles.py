"""jit-cache misses (``jax.monitoring`` backend-compile events) plus the
serving layer's own compile counter, inside the window."""


def read(obs):
    return obs["compiles_in_window"]
