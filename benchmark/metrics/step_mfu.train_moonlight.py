"""The whole step's share of the chip's peak in a Moonlight training cell:
the operations forward and backward of a token REQUIRE
(benchmark/arch/deepseek_v3/roofline.py: the matrices with the held experts
at the pairs a token the program counted, latent attention at 16 x
(192 + 128) over half the sequence in every layer; recomputation and the
rotation not counted) times the tokens per second per chip of the run's
window, over the peak. None where the program is not this architecture's.
Layer: train step. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.deepseek_v3 import readers


def read(run):
    return readers.step_mfu(run)
