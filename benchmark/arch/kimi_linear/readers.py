"""What the per-layer readers of this architecture's cells share: a kernel's
device seconds and its OWN events in the traced slice. A share of a roofline
is (steps the kernel ran in the slice) x (least seconds a step) over the
kernel's device seconds, the steps counted from the kernel's events and how
many a step has, never from the programs in the slice (PERF.md section 7)."""
from __future__ import annotations

from benchmark import named, roofline

FLASH = ("flash_fwd", "flash_dq", "flash_dkv")
KDA = ("kda_fwd", "kda_bwd")
GMM = ("grouped_matmul", "grouped_matmul_dw")


def events(reduced: dict, *names: str) -> float:
    """How often the kernels called any of `names` ran, a device."""
    return sum(n for op, n in reduced["op_n"].items()
               if any(named.is_kernel(op, name) for name in names))


def share(run: dict, least_per_step: float, events_per_step, *names: str):
    """100 x steps x least / device seconds, or None where the kernel did
    not run (a program without it) or did not say how it ran."""
    trace = run.get("trace")
    if not trace or not events_per_step:
        return None
    spent, ran = named.kernel_seconds(trace, *names), events(trace, *names)
    if not spent or not ran:
        return None
    return 100.0 * (ran / events_per_step) * least_per_step / spent


def least(run: dict, work: tuple[float, float]) -> float:
    return roofline.least_seconds(*work, roofline.peaks(run["device"]["kind"]))[0]


def kda_calls_per_layer(run: dict) -> float | None:
    """The scan kernels walk a block of heads a call. The block is what the
    program resolved (`last_resolution("kda")`, carried by the run): the
    yardstick keeps no copy of the rule."""
    block = (run.get("resolutions") or {}).get("kda", {}).get("head_block")
    if not block:
        return None
    cell = run["cell"]
    return cell["mix"]["rows"] * cell["model"]["linear_attn_config"]["num_heads"] / block


def routed_rows_per_layer(run: dict) -> float | None:
    """Token-expert pairs routed to the held experts, a step and expert
    layer: the program's count over the steps of the traced slice, where it
    settled any, else over the window's."""
    moe = run.get("moe_slice") or {}
    if not moe.get("steps"):
        moe = run.get("moe") or {}
    if not moe.get("steps"):
        return None
    from benchmark.arch.kimi_linear import roofline as KR

    return moe["routed_slots"] / moe["steps"] / KR.n_layers(run["cell"]["model"], "moe")
