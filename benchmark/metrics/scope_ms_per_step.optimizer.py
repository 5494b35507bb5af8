"""Device milliseconds a step of the update after the gradients: AdamW,
clipping, the step's health and telemetry: the `optimizer` part of the step
program (`benchmark/scopes.py`). Layer: train step. Moves
train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "optimizer")
