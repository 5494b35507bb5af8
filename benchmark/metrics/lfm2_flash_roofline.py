"""Flash attention at 32 query heads on 8 key heads of width 64, half the
chip's lanes (kernels `flash_fwd`, `flash_dq`, `flash_dkv`): least time for
the attention layers' causal attention forward and backward over the steps
`flash_dq` ran in the slice, over the three kernels' device time
(benchmark/arch/lfm2_moe/readers.py). None where the kernels did not run.
Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.lfm2_moe import readers


def read(run):
    return readers.flash_share(run)
