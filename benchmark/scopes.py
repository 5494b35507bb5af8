"""Device time a step by the part of the model the program names: the trace's
`XLA Ops` events joined against the table the program publishes
(`paddle_tpu.observability.scopes.last_table()`: instruction name -> part).

An event is named by its whole HLO instruction (`%fusion.374 = bf16[...]
fusion(...)`); its head before ` = `, without the `%`, is the instruction's
name in the compiled program, and the table says which part it belongs to. The
events counted are those of the step program's runs (`XLA Modules` events
whose name is the table's module, `jit__step_fn(<hash>)`) inside the traced
slice, clipped to both. Each instant of their busy union goes to the INNERMOST
event over it (the one that started last): a `while` keeps only the time its
body's operations leave, so the parts partition the busy union and nothing is
counted twice. Seconds by part are averaged over the device planes and divided
by the steps the slice holds (`reduce.steps_measured`).

None, never an exception, where there is nothing to read: no trace, a program
without the table (`paddle_tpu.observability.scopes` is absent, or built no
step), a table of a module the trace does not hold, no whole step.
"""
from __future__ import annotations

import bisect
import heapq

from benchmark import reduce

_cache: tuple | None = None


def _program_table() -> dict | None:
    try:
        from paddle_tpu.observability import scopes
    except ImportError:
        return None
    return scopes.last_table()


def _is_run_of(module: str, name: str) -> bool:
    return name == module or name.startswith(module + "(")


def _pieces(events, runs):
    """(name, start, end) of the events' parts inside the sorted, disjoint
    intervals `runs`."""
    starts = [a for a, _ in runs]
    for n, s, d in events:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(runs) and runs[i][0] < s + d:
            a, b = max(s, runs[i][0]), min(s + d, runs[i][1])
            if b > a:
                yield n, a, b
            i += 1


def innermost_seconds(pieces) -> dict[str, float]:
    """{event name: nanoseconds of the busy union in which it is the
    innermost event}: at each instant the covering event that started last
    (the shorter on a tie) takes it."""
    pieces = sorted(pieces, key=lambda p: (p[1], -p[2]))
    bounds = sorted({t for _, a, b in pieces for t in (a, b)})
    out: dict[str, float] = {}
    active: list = []                  # (-start, end, order, name)
    j = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while j < len(pieces) and pieces[j][1] <= t0:
            n, a, b = pieces[j]
            heapq.heappush(active, (-a, b, j, n))
            j += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            n = active[0][3]
            out[n] = out.get(n, 0.0) + (t1 - t0)
    return out


def step_pieces(trace: dict, module: str, plane: str) -> list | None:
    """(event name, start, end) of a plane's `XLA Ops` events as far as they
    lie inside the slice and inside the runs of `module`; None where the
    plane holds no such run."""
    lo, hi = trace["interval_ns"]
    dev = trace["events"]["devices"][plane]
    runs = sorted((max(s, lo), min(s + d, hi)) for n, s, d in dev.get("modules", [])
                  if _is_run_of(module, n) and min(s + d, hi) > max(s, lo))
    return list(_pieces(dev["ops"], runs)) if runs else None


def ms_per_step(run: dict) -> dict[str, float] | None:
    """{part: device milliseconds a step} of a traced run, `unscoped` for the
    operations the table does not place; None where there is nothing to
    read."""
    global _cache
    trace = run.get("trace")
    table = _program_table()
    if not trace or not table or not table.get("ops"):
        return None
    if _cache is not None and _cache[0] is trace and _cache[1] is table:
        return _cache[2]
    steps = reduce.steps_measured(trace)
    planes = trace["planes"]
    total: dict[str, float] = {}
    for plane in planes:
        pieces = step_pieces(trace, table["module"], plane)
        if pieces is None:
            total = {}
            break
        for event, ns in innermost_seconds(pieces).items():
            part = table["ops"].get(event.split(" = ")[0].lstrip("%"), "unscoped")
            total[part] = total.get(part, 0.0) + ns
    out = ({part: ns / 1e6 / len(planes) / steps for part, ns in total.items()}
           if total and steps else None)
    _cache = (trace, table, out)
    return out


def part_ms_per_step(run: dict, part: str) -> float | None:
    """Device milliseconds a step of one part (0.0 where it ran nothing)."""
    parts = ms_per_step(run)
    return None if parts is None else parts.get(part, 0.0)
