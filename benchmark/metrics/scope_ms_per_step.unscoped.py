"""Device milliseconds a step of the step's operations that no named part
claims (XLA's copies of parameters, constants); a large value means a part
lost its name: the `unscoped` part of the step program
(`benchmark/scopes.py`). Layer: device. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "unscoped")
