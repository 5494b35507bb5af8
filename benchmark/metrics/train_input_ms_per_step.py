"""What a training step spends getting its batch onto the device: the
program's `train.place` spans (CompiledTrainStep.__call__, on the profiler's
clock) in the traced window, over the steps the device ran in it. Layer:
train step. Moves train_tokens_per_s_per_chip."""
from benchmark import named


def read(run):
    return named.host_ms_per_step(run, "train.place")
