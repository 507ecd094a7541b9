"""XLA compiles in the measured window, loads from the persistent cache
included (``jax.monitoring``'s backend-compile event wraps both)."""


def read(win):
    return float(win.compiles)
