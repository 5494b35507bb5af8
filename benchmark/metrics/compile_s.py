"""Seconds JAX spent tracing, lowering and compiling (or fetching from the
persistent cache) during set-up, by jax.monitoring's own durations.
Layer: compile cache. Moves setup_s."""


def read(run):
    return run["compile_s"]
