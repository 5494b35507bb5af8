"""What the per-layer readers of this architecture's cells share. A share of
a roofline is (steps the kernel ran in the slice) x (least seconds a step)
over the kernel's device seconds, the steps counted from the kernel's OWN
events and how many a step has, never from the programs in the slice
(PERF.md section 7). Every reader returns None where the run is of another
architecture or its kernel, counter or trace is absent: a program without
them prints a line without the metric. No model code is imported here."""
from __future__ import annotations

from benchmark import named
from benchmark.arch.deepseek_v3 import roofline as DR
from benchmark.arch.kimi_linear.readers import FLASH, GMM, events, least

NAMED = (*FLASH, *GMM, "ce_stats")


def ours(run: dict) -> bool:
    """Whether the run is of this architecture: the readers count its layers."""
    return (run.get("cell") or {}).get("model", {}).get("arch") == "deepseek_v3"


def _rows_per_layer(moe: dict | None, run: dict) -> float | None:
    if not moe or not moe.get("steps") or not moe.get("routed_slots") or not ours(run):
        return None
    return moe["routed_slots"] / moe["steps"] / DR.n_layers(run["cell"]["model"], "moe")


def routed_rows_per_layer(run: dict) -> float | None:
    """Token-expert pairs routed to the held experts, a step and expert
    layer: the program's count over the steps of the traced slice, where it
    settled any, else over the window's."""
    return _rows_per_layer(run.get("moe_slice"), run) or _rows_per_layer(run.get("moe"), run)


def pairs_per_token(run: dict) -> float | None:
    """Of a token's `top_k` experts, how many are held here, a layer: the
    program's count over the window's settled steps."""
    rows = _rows_per_layer(run.get("moe"), run)
    return None if rows is None else rows / run["tokens_per_step"]


def flash_share(run: dict) -> float | None:
    """Flash at 16 heads of 192/128: least time of ONE layer's required
    forward and backward, times the `flash_dq` events in the slice (one a
    layer and step: six a step), over the three kernels' device time. The
    required work counts 192 and 128 whatever the kernels pad to."""
    trace = run.get("trace")
    if not trace or not ours(run):
        return None
    spent, ran = named.kernel_seconds(trace, *FLASH), events(trace, "flash_dq")
    if not spent or not ran:
        return None
    cell = run["cell"]
    rows, seq = cell["mix"]["rows"], cell["mix"]["seq_len"]
    return 100.0 * ran * (least(run, DR.flash_fwd(cell["model"], rows, seq))
                          + least(run, DR.flash_bwd(cell["model"], rows, seq))) / spent


def expert_gmm_share(run: dict) -> float | None:
    """The nine grouped products of ONE expert layer over the pairs the
    program counted, times the layers and steps the kernels ran in the slice
    (three `grouped_matmul_dw` events an expert layer and step), over the
    kernels' device time."""
    rows, trace = routed_rows_per_layer(run), run.get("trace")
    if rows is None or not trace:
        return None
    spent, ran = named.kernel_seconds(trace, *GMM), events(trace, "grouped_matmul_dw") / 3
    if not spent or not ran:
        return None
    return 100.0 * ran * least(run, DR.expert_gmm(run["cell"]["model"], rows)) / spent


def named_kernels_share(run: dict) -> float | None:
    """Share of the device's busy time in the slice that the named kernels
    take (flash, the grouped products, `ce_stats`)."""
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not ours(run):
        return None
    spent = named.kernel_seconds(trace, *NAMED)
    return 100.0 * spent / trace["busy_s"] if spent else None


def step_mfu(run: dict) -> float | None:
    """Required operations a token (the held experts at the pairs the
    program counted) x tokens/s/chip of the run's window over the peak."""
    if not ours(run) or not run.get("tokens_per_s_per_chip"):
        return None
    from benchmark import roofline

    cell = run["cell"]
    per_token = DR.train_flops_per_token(cell["model"], cell["mix"]["seq_len"],
                                         pairs_per_token(run))
    return 100.0 * per_token * run["tokens_per_s_per_chip"] \
        / roofline.peaks(run["device"]["kind"])["bf16_flops_per_s"]
