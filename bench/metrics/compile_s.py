"""Seconds of lowering and compiling during the run's set-up, from
jax.monitoring's compile events (with a warm cache: lowering and loading)."""


def read(m):
    return m.compile_s
