"""The whole step's share of the chips' peak: the operations forward and
backward of a token REQUIRE (roofline.py; recomputation not counted) times
the tokens per second per chip of the traced run's window, over the peak.
Layer: train step. Moves train_tokens_per_s_per_chip."""
from benchmark import roofline


def read(run):
    cell = run["cell"]
    peak = roofline.peaks(run["device"]["kind"])
    per_token = roofline.train_flops_per_token(cell["model"], cell["mix"]["seq_len"])
    return 100.0 * per_token * run["tokens_per_s_per_chip"] / peak["bf16_flops_per_s"]
