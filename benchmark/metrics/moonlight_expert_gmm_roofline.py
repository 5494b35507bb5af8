"""The grouped products of the held experts of a Moonlight cell (kernels
`grouped_matmul`, `grouped_matmul_dw`; 2048 x 1408, 6 experts a token): least
time for the nine products of an expert layer over the token-expert pairs
the program counted as routed here (`moe.routed_slots`, the slice's mean a
step), over the layers and steps the kernels ran in the slice (three
`grouped_matmul_dw` events an expert layer and step), over the kernels'
device time; the forward products made again in the backward pass lower the
share (benchmark/arch/deepseek_v3/readers.py). None without the counter or
the kernels. Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.deepseek_v3 import readers


def read(run):
    return readers.expert_gmm_share(run)
