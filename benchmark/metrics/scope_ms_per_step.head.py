"""Device milliseconds a step of the final norm, the head, the loss (`ce_stats`
and the head's backward loop included): the `head` part of the step program
(`benchmark/scopes.py`). Layer: model. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "head")
