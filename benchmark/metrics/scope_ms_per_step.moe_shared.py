"""Device milliseconds a step of the shared experts: the `moe_shared` part of
the step program (`benchmark/scopes.py`). Layer: experts. Moves
train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "moe_shared")
