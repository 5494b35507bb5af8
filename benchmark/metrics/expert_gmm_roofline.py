"""The grouped products of the held experts (kernels `grouped_matmul`,
`grouped_matmul_dw`): least time for the nine products of an expert layer
over the token-expert pairs the program counted as routed here
(`moe.routed_slots`, the slice's mean a step), over the steps the kernels ran
in the slice, over the kernels' device time. A step of an expert layer is
three `grouped_matmul_dw` events (the gradients of gate, up and down: the one
kernel nothing makes twice); the forward products made again in the backward
pass lower the share. Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark import named
from benchmark.arch.kimi_linear import readers, roofline as KR


def read(run):
    rows = readers.routed_rows_per_layer(run)
    trace = run.get("trace")
    if rows is None or not trace:
        return None
    cell = run["cell"]
    layers = KR.n_layers(cell["model"], "moe")
    per_step = layers * readers.least(run, KR.expert_gmm(cell["model"], rows))
    steps = readers.events(trace, "grouped_matmul_dw") / (3 * layers)
    spent = named.kernel_seconds(trace, *readers.GMM)
    if not spent or not steps:
        return None
    return 100.0 * steps * per_step / spent
