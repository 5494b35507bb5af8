"""The fused head + softmax statistics kernel (`_ce_stats_kernel`) in the
train step: least time for the head's product over the tokens of the steps
traced, over the kernel's device time. Compute-bound. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark import reduce, roofline


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    cfg = run["cell"]["model"]
    # told by its operand: the head's weights [hidden, vocab / shards]
    shards = (run["cell"].get("mesh") or {}).get("mp", 1)
    spent = reduce.pallas_seconds(trace, has=reduce.dims(cfg["hidden_size"], cfg["vocab_size"] // shards))
    steps = reduce.main_module_runs(trace)
    if not spent or not steps:
        return None
    cell, peak = run["cell"], roofline.peaks(run["device"]["kind"])
    tokens = run["tokens_per_step"] / cell["chips"]
    flops, bytes_ = roofline.fused_ce(cell["model"], tokens)
    return 100.0 * steps * roofline.least_seconds(flops, bytes_, peak)[0] / spent
