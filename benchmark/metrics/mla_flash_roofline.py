"""Flash attention on the latent-attention shapes (query/key width 192, value
width 128; kernels `flash_fwd`, `flash_dq`, `flash_dkv`): least time for the
MLA layers' causal attention forward and backward, each over the steps ITS
kernel ran in the slice, over the three kernels' device time. The required
work counts 192 and 128 whatever the kernels pad to. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark import named
from benchmark.arch.kimi_linear import readers, roofline as KR


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    cell = run["cell"]
    layers = KR.n_layers(cell["model"], "mla")
    spent = named.kernel_seconds(trace, *readers.FLASH)
    fwd_steps = readers.events(trace, "flash_fwd") / layers
    bwd_steps = readers.events(trace, "flash_dkv") / layers
    if not spent or not (fwd_steps or bwd_steps):
        return None
    rows, seq = cell["mix"]["rows"], cell["mix"]["seq_len"]
    fwd = readers.least(run, KR.mla_flash_fwd(cell["model"], rows, seq))
    bwd = readers.least(run, KR.mla_flash_bwd(cell["model"], rows, seq))
    return 100.0 * layers * (fwd_steps * fwd + bwd_steps * bwd) / spent
