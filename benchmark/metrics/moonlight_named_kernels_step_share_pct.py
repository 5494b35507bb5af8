"""Share of the device's busy time in the traced slice that the NAMED
kernels of a Moonlight step take: flash, the experts' grouped products and
`ce_stats`. 100 less this is what XLA's own fusions take: the q/K/V build
around flash (projections, rotation, broadcast, concatenation, layout), the
dense and shared feed-forwards, the expert layout's row movers' neighbours,
the head's backward, the optimizer. None without a trace or the kernels.
Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.deepseek_v3 import readers


def read(run):
    return readers.named_kernels_share(run)
