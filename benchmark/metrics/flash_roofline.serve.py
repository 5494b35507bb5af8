"""Flash attention in the prefill frames (`_fwd_kernel`): least time for the
causal attention of the prompts admitted in the traced slice (compute-bound
for long prompts), over the kernel's device time. The engine runs each
chunk over its whole padded context bucket, which this does not excuse.
Layer: kernels. Moves ttft_p95_ms."""
from benchmark import reduce, roofline


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    cfg0, eng = run["cell"]["model"], run["cell"]["engine"]
    pool = reduce.dims(cfg0["num_key_value_heads"], eng["num_pages"], eng["page_size"],
                       cfg0["head_dim"])
    spent = reduce.pallas_seconds(trace, lacks=pool)   # every Pallas call but paged decode
    if not spent:
        return None
    cfg, peak = run["cell"]["model"], roofline.peaks(run["device"]["kind"])
    # the program's records are on the host's clock, and so is this interval
    lo, hi = run["trace_t0"], run["trace_t0"] + run["trace_host_window_s"]
    least = sum(roofline.least_seconds(*roofline.flash_fwd(cfg, 1, n), peak)[0]
                for t, n in run["admitted"] if lo <= t <= hi)
    if not least:
        return None
    return 100.0 * cfg["num_hidden_layers"] * least / spent
