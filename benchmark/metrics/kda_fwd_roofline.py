"""The forward scan of Kimi Delta Attention (kernel `kda_fwd`): least time
for the sequential part of the KDA layers over the steps the kernel ran in
the slice, over its device time. The kernel runs twice a step and layer
(once more in the backward pass, which keeps no chunk: time spent, not work
required), so two events of a block of heads are one required pass. Memory-
bound. Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark.arch.kimi_linear import readers, roofline as KR


def read(run):
    cell = run["cell"]
    tokens = cell["mix"]["rows"] * cell["mix"]["seq_len"]
    layers = KR.n_layers(cell["model"], "kda")
    per_step = layers * readers.least(run, KR.kda_scan_fwd(cell["model"], tokens))
    return readers.share(run, per_step, 2 * layers * (readers.kda_calls_per_layer(run) or 0),
                         "kda_fwd")
