"""Device milliseconds a step of the attention mixers: latent or grouped-query
attention with its pre-norm and residual add, flash included, forward and
backward: the `attn` part of the step program (`benchmark/scopes.py`).
Layer: model. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "attn")
