"""The whole step's share of the chip's peak in a Kimi-Linear training cell:
the operations forward and backward of a token REQUIRE
(benchmark/arch/kimi_linear/roofline.py: the matrices with the held experts
at the pairs a token the program counted, latent attention over half the sequence, the
chunked delta rule; recomputation not counted) times the tokens per second
per chip of the traced run's window, over the peak. Layer: train step. Moves
train_tokens_per_s_per_chip."""
from benchmark import roofline
from benchmark.arch.kimi_linear import roofline as KR


def read(run):
    cell = run["cell"]
    peak = roofline.peaks(run["device"]["kind"])
    moe, pairs = run.get("moe") or {}, None
    if moe.get("steps"):        # the token-expert pairs the program counted here
        pairs = moe["routed_slots"] / moe["steps"] / run["tokens_per_step"] \
            / KR.n_layers(cell["model"], "moe")
    per_token = KR.train_flops_per_token(cell["model"], cell["mix"]["seq_len"], pairs)
    return 100.0 * per_token * run["tokens_per_s_per_chip"] / peak["bf16_flops_per_s"]
