"""The paged decode kernel (`_decode_kernel`): least time to read once every
cached key and value that the tokens delivered in the traced slice attended
to (memory-bound), over the kernel's device time. Layer: kernels. Moves
itl_p95_ms."""
from benchmark import reduce, roofline


def _pool(run) -> str:
    """The operand only the paged kernel has: one layer's page pool."""
    cfg, eng = run["cell"]["model"], run["cell"]["engine"]
    return reduce.dims(cfg["num_key_value_heads"], eng["num_pages"], eng["page_size"],
                       cfg["head_dim"])


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    spent = reduce.pallas_seconds(trace, has=_pool(run))
    if not spent:
        return None
    cfg, peak = run["cell"]["model"], roofline.peaks(run["device"]["kind"])
    # the program's records are on the host's clock, and so is this interval
    lo, hi = run["trace_t0"], run["trace_t0"] + run["trace_host_window_s"]
    context = batch = 0
    for rec in run["records"]:
        n0 = len(rec["prompt"])
        for i, t in enumerate(rec["times"]):
            if lo <= t <= hi:
                context += n0 + i
                batch += 1
    flops, bytes_ = roofline.paged_decode(cfg, context, batch)
    least = cfg["num_hidden_layers"] * roofline.least_seconds(flops, bytes_, peak)[0]
    return 100.0 * least / spent
