"""The backward scan of Kimi Delta Attention (kernel `kda_bwd`): least time
for it over the steps the kernel ran in the slice, over its device time.
Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.kimi_linear import readers, roofline as KR


def read(run):
    cell = run["cell"]
    tokens = cell["mix"]["rows"] * cell["mix"]["seq_len"]
    layers = KR.n_layers(cell["model"], "kda")
    per_step = layers * readers.least(run, KR.kda_scan_bwd(cell["model"], tokens))
    return readers.share(run, per_step, layers * (readers.kda_calls_per_layer(run) or 0), "kda_bwd")
