"""Device milliseconds a step of the token embedding, forward and backward: the
`embed` part of the step program (`benchmark/scopes.py`). Layer: model.
Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "embed")
