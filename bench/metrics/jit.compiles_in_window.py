"""XLA compiles inside the window, counted through ``jax.monitoring``
(backend compiles less loads from the persistent cache)."""


def read(window):
    return float(window.compiles)
