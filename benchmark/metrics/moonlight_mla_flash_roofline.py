"""Flash attention at 16 heads of query/key width 192 beside value width 128
(kernels `flash_fwd`, `flash_dq`, `flash_dkv`), in every layer of a
Moonlight cell: least time for one layer's causal attention forward and
backward over the layers and steps `flash_dq` ran in the slice (six a
step), over the three kernels' device time
(benchmark/arch/deepseek_v3/readers.py). The required work counts 192 and
128 whatever the kernels pad to. None where the kernels did not run or the
program is not this architecture's. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark.arch.deepseek_v3 import readers


def read(run):
    return readers.flash_share(run)
