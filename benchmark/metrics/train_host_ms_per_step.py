"""The time the host WORKS for a training step, not the time it waits: the
program's `train.place` + `train.dispatch` spans in the traced window (the
batch to the device; key, learning rate, the call of the compiled step and
taking over its outputs), over the steps the device ran in it. The wait on
the run-ahead window (`train.run_ahead_wait`) is left out. Layer: train
step. Moves train_tokens_per_s_per_chip."""
from benchmark import named


def read(run):
    return named.host_ms_per_step(run, "train.place", "train.dispatch")
