"""Programs compiled, or loaded from the persistent cache, inside the
traced window (jax.monitoring's backend-compile events).  Set-up warms
every program the window runs, so this should read 0."""


def read(r):
    return float(r.compiles)
