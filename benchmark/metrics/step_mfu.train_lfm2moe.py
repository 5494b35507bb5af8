"""The whole step's share of the chip's peak in an LFM2-MoE training cell:
the operations forward and backward of a token REQUIRE
(benchmark/arch/lfm2_moe/roofline.py: the matrices with the held experts at
the pairs a token the program counted, grouped-query attention over half the
sequence; recomputation not counted) times the tokens per second per chip of
the traced run's window, over the peak. None where the program is not this
architecture's. Layer: train step. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.lfm2_moe import readers


def read(run):
    return readers.step_mfu(run)
