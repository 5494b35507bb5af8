"""Device milliseconds a step of the held experts' three grouped products and
the SwiGLU between them: the `moe_experts` part of the step program
(`benchmark/scopes.py`). Layer: experts. Moves train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "moe_experts")
