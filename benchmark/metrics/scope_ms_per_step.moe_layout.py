"""Device milliseconds a step of the held experts' layout: the index arrays and
the row movers that fill the buffer and sum the products back, on either
backend: the `moe_layout` part of the step program (`benchmark/scopes.py`).
Layer: experts. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "moe_layout")
