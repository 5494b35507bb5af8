"""The whole serving loop's share of the chip's peak: the operations the
prompts admitted and the tokens delivered in the measured window REQUIRE
(roofline.py) over the window times the peak. Layer: serving engine. Moves
serve_out_tokens_per_s."""
from benchmark import roofline


def read(run):
    cfg = run["cell"]["model"]
    peak = roofline.peaks(run["device"]["kind"])
    lo, hi = run["t0"], run["t0"] + run["window_s"]
    flops = sum(roofline.prefill_flops(cfg, n) for t, n in run["admitted"] if lo <= t <= hi)
    for rec in run["records"]:
        n0 = len(rec["prompt"])
        flops += sum(roofline.decode_flops(cfg, n0 + i)
                     for i, t in enumerate(rec["times"]) if lo <= t <= hi)
    return 100.0 * flops / (run["window_s"] * peak["bf16_flops_per_s"])
