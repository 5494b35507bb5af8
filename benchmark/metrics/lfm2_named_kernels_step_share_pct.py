"""Share of the device's busy time in the traced slice that the NAMED
kernels of an LFM2-MoE step take: flash, the experts' grouped products and
`ce_stats`. 100 less this is what XLA's own fusions take: the convolution
operators, the dense feed-forward, the expert layout's gathers and scatters,
the head's backward, the optimizer. None without a trace or the kernels.
Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.lfm2_moe import readers


def read(run):
    return readers.named_kernels_share(run)
