"""Programs compiled, or loaded from the persistent cache, inside the
window (jax.monitoring events).  Expected 0: set-up warms every shape.
Layer: dispatch.  Moves itl_p95_ms."""


def read(run):
    return float(run.compiles_in_window)
