"""Flash attention forward + backward in the train step: the least time the
chip could take for the attention of the steps traced (roofline.py: the
larger of operations over peak and bytes over bandwidth; compute-bound at
4096) over the device time of the step's Pallas kernels that do not read the
head's [hidden, vocab] weights: `_fwd_kernel`, `_dq_kernel`, `_dkv_kernel`.
Layer: kernels. Moves train_tokens_per_s_per_chip."""
from benchmark import reduce, roofline

def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    cfg = run["cell"]["model"]
    shards = (run["cell"].get("mesh") or {}).get("mp", 1)
    spent = reduce.pallas_seconds(trace, lacks=reduce.dims(cfg["hidden_size"], cfg["vocab_size"] // shards))
    steps = reduce.steps_measured(trace)
    if not spent or not steps:
        return None
    cell, peak = run["cell"], roofline.peaks(run["device"]["kind"])
    fwd = roofline.flash_fwd(cell["model"], cell["mix"]["rows"], cell["mix"]["seq_len"])
    bwd = roofline.flash_bwd(cell["model"], cell["mix"]["rows"], cell["mix"]["seq_len"])
    # the whole batch's attention, shared evenly by the chips
    least = cell["model"]["num_hidden_layers"] * steps / cell["chips"] * (
        roofline.least_seconds(*fwd, peak)[0] + roofline.least_seconds(*bwd, peak)[0])
    return 100.0 * least / spent
