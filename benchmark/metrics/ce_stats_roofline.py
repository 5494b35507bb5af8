"""The fused head + softmax statistics kernel in the train step, told by the
name the program gives it (`ce_stats`): least time for the head's product
over the tokens of the steps traced, over the kernel's device time.
Compute-bound. Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark import named, roofline


def read(run):
    cell = run["cell"]
    peak = roofline.peaks(run["device"]["kind"])
    work = roofline.fused_ce(cell["model"], run["tokens_per_step"] / cell["chips"])
    return named.roofline_share(run, roofline.least_seconds(*work, peak)[0], "ce_stats")
