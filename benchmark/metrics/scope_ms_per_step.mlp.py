"""Device milliseconds a step of the feed-forward sub-layers outside the
experts' own parts: the dense SwiGLU, and each expert layer's pre-norm,
residual add and casts: the `mlp` part of the step program
(`benchmark/scopes.py`). Layer: model. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "mlp")
