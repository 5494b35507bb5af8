"""Device milliseconds a step of the gated short-convolution operators with
their pre-norm and residual add: the `conv_mixer` part of the step program
(`benchmark/scopes.py`). Layer: model. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "conv_mixer")
