"""Device milliseconds a step of the experts' router: its scores, the top-k
choice and the balancing rule's count: the `moe_router` part of the step
program (`benchmark/scopes.py`). Layer: experts. Moves
train_tokens_per_s_per_chip."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_step(run, "moe_router")
