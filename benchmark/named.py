"""What the program itself names, read from a reduced trace: device seconds
of a Pallas kernel by the name its `pallas_call` carries, and host seconds of
the program's own spans.

A kernel named `flash_fwd` by the program (`name=` and
`metadata={"kernel": ...}` of its `pallas_call`) reaches a v5e trace twice in
the one event (looked at by hand, PERF.md section 3): the HLO instruction is
itself called `%flash_fwd.3`, and its text carries
`frontend_attributes={kernel_metadata={"kernel":"flash_fwd"}}`. Either tells
the kernel; no operand does. A program that names nothing (the parent of the
PR that brought the names) matches nothing here, and the readers return None.
"""
from __future__ import annotations

import re

from benchmark import reduce


def is_kernel(instruction: str, name: str) -> bool:
    """Whether a device event (named by its whole HLO instruction) is the
    Pallas kernel the program called `name`."""
    if reduce.PALLAS_CALL not in instruction:
        return False
    return bool(re.match(rf"%?{re.escape(name)}(\.[\w.]+)? = ", instruction)
                or re.search(rf'"kernel"\s*:\s*"{re.escape(name)}"', instruction))


def kernel_seconds(reduced: dict, *names: str) -> float:
    """Device seconds (a device) of the kernels called any of `names`."""
    return sum(v for n, v in reduced["op_s"].items()
               if any(is_kernel(n, name) for name in names))


def host_seconds(reduced: dict, *names: str) -> float | None:
    """Seconds of the host spans called any of `names` inside the traced
    slice, or None where the program wrote no such span."""
    lo, hi = reduced["interval_ns"]
    durs = [min(s + d, hi) - max(s, lo) for n, s, d in reduced["events"]["host"] if n in names]
    return sum(d for d in durs if d > 0) / 1e9 if durs else None


def host_ms_per_step(run: dict, *names: str) -> float | None:
    """Milliseconds a step of the host spans called `names`, over the steps
    the device ran in the traced slice (`reduce.steps_measured`); None
    without such spans or without a whole step in the slice."""
    trace = run.get("trace")
    if not trace:
        return None
    spent, steps = host_seconds(trace, *names), reduce.steps_measured(trace)
    if spent is None or not steps:
        return None
    return 1e3 * spent / steps


def roofline_share(run: dict, least_per_step: float, *names: str) -> float | None:
    """100 x (least seconds for the steps traced) / (device seconds of the
    kernels called `names`), steps and seconds both of what ran inside the
    slice; None where nothing is so called or the slice holds no whole step."""
    trace = run.get("trace")
    if not trace:
        return None
    spent = kernel_seconds(trace, *names)
    steps = reduce.steps_measured(trace)
    if not spent or not steps:
        return None
    return 100.0 * steps * least_per_step / spent
