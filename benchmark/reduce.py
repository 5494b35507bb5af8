"""From the profiler's xplane to numbers: device busy time, time by device
operation, collectives and their exposed part, the longest idle gaps named
by what the host was doing.

Two stages, so that the arithmetic can be checked on a small recorded trace
(`tests/data/`): `load_xplane` turns the file into plain lists of
[name, start_ns, duration_ns]; `reduce_events` turns those into numbers.

ONE interval on the trace's own clock bounds every number: the slice
`[lo, hi]` that the harness marked with its `bench.slice` span (the host's
and the device's planes share a timeline: a `train.read_loss` ends 1.4 to
1.7 ms after the device's end of the step it waited for, PERF.md section
6), from the device's first recorded event on, or, in a trace without the
span, the first device operation's start to the last one's end. Every
device event is clipped to it before anything is summed, so the busy union
cannot pass the window, whatever the profiler's stopping costs; the steps
of the slice are the fraction of the step program that ran inside it
(`main_module_runs`).

What a v5e trace looks like (looked at by hand, PERF.md section 3): device
planes are named `/device:TPU:<n>`; their line `XLA Ops` holds one event for
every HLO operation that ran, named by the whole text of the HLO instruction
(`%fusion.52 = bf16[...] fusion(...)`, `%fn.24 = ... custom-call(...),
custom_call_target="tpu_custom_call"` for a Pallas kernel); the line
`XLA Modules` holds one event a program run (`jit_fn(<hash>)`). The host plane is `/host:CPU`; `TraceAnnotation`s are events on its
thread lines under the name given.
"""
from __future__ import annotations

import glob
import os
import statistics

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("train.", "engine.", "loadgen.", "bench.")
SLICE_SPAN = "bench.slice"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        out["lines"][plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [[e.name, float(e.start_ns), float(e.duration_ns)]
                                for e in line.events if e.name.startswith(HOST_SPANS)]
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _minus(a, b) -> float:
    """Length of the union `a` outside the union `b`."""
    total, j = 0.0, 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            total += hi - cur
    return total


def is_collective(name: str) -> bool:
    """`%all-reduce.3 = ...`: the instruction's own name says what it is."""
    return name.lstrip("%").startswith(COLLECTIVES)


def slice_interval(events: dict, planes: list[str]) -> tuple[float, float]:
    """The traced slice `[lo, hi]` in the trace's nanoseconds: the harness's
    `bench.slice` span from where the device's record begins (the device is
    traced from 1.7 ms before to 7.4 ms after the span opens, PERF.md section 6,
    and says nothing of the time before its first event; at the far end the
    profiler runs on past the span, so a device that falls silent before
    `hi` is idle) or, without the span, the device operations' own extent."""
    evs = [(s, s + d) for p in planes for _, s, d in events["devices"][p]["ops"]]
    first = min((s for s, _ in evs), default=None)
    for n, s, d in events["host"]:
        if n == SLICE_SPAN:
            return (s if first is None else max(s, first)), s + d
    return (first, max(e for _, e in evs)) if evs else (0.0, 0.0)


def _clip(evs: list, lo: float, hi: float) -> list[tuple[str, float, float, float]]:
    """(name, start, end, share) of the events' parts inside `[lo, hi]`,
    `share` the part's length over the event's."""
    out = []
    for n, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b, (b - a) / d))
    return out


def reduce_events(events: dict, n_devices: int | None = None) -> dict:
    """Numbers of one traced slice, seconds throughout, averaged over the
    device planes, every device event clipped to the slice first: window_s
    (the slice's length) and busy_s; op_s {operation name: seconds} and op_n
    (events, a cut one counted by the part inside); module_s, module_whole_s
    (the lengths of a program's runs that were not cut) and module_longest_s;
    collective_s and exposed_collective_s; device_ops and idle_gaps as the
    result line's `breakdown` wants them."""
    planes = sorted(events["devices"])
    if n_devices:
        planes = planes[:n_devices]
    lo, hi = slice_interval(events, planes)
    busy = coll = exposed = 0.0
    op_s: dict[str, float] = {}
    op_n: dict[str, float] = {}
    module_s: dict[str, float] = {}
    module_whole_s: dict[str, list[float]] = {}
    module_longest_s: dict[str, float] = {}
    first_busy = None if planes else []      # no device plane (a CPU): nothing ran
    for plane in planes:
        ops = _clip(events["devices"][plane]["ops"], lo, hi)
        compute = _union([(a, b) for n, a, b, _ in ops if not is_collective(n)])
        comm = _union([(a, b) for n, a, b, _ in ops if is_collective(n)])
        both = _union(compute + comm)
        busy += _length(both)
        coll += _length(comm)
        exposed += _minus(comm, compute)
        for n, a, b, share in ops:
            op_s[n] = op_s.get(n, 0.0) + (b - a)
            op_n[n] = op_n.get(n, 0.0) + share / len(planes)
        for n, a, b, _ in _clip(events["devices"][plane].get("modules", []), lo, hi):
            module_s[n] = module_s.get(n, 0.0) + (b - a)
            module_longest_s[n] = max(module_longest_s.get(n, 0.0), (b - a) / 1e9)
            if a > lo and b < hi:       # not cut: it touches neither end of the slice
                module_whole_s.setdefault(n, []).append((b - a) / 1e9)
        if first_busy is None:
            first_busy = both
    k = max(len(planes), 1) * 1e9
    # idle gaps of the first device, the slice's two ends included, named by
    # the host span they fall in
    host = sorted((e for e in events["host"] if e[0] != SLICE_SPAN),
                  key=lambda e: e[2])                   # the shortest span wins
    edges = [(lo, lo)] + first_busy + [(hi, hi)]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:])
                   if b[0] > a[1]), reverse=True)[:10]
    idle = []
    for length, a, b in gaps:
        mid = (a + b) / 2
        inside = [n for n, s, d in host if s <= mid <= s + d]
        idle.append([inside[0] if inside else "host:unnamed", length / 1e9])
    # a device event is named by its whole HLO instruction: keep its head
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    top = [(n.split(" custom_call_target")[0][:160] + (" <pallas>" if PALLAS_CALL in n else ""), v)
           for n, v in top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / k,
            "op_s": {n: v / k for n, v in op_s.items()}, "op_n": op_n,
            "module_s": {n: v / k for n, v in module_s.items()},
            "module_whole_s": module_whole_s, "module_longest_s": module_longest_s,
            "collective_s": coll / k, "exposed_collective_s": exposed / k,
            "device_ops": [[n, v / k] for n, v in top], "idle_gaps": idle,
            "planes": planes, "interval_ns": [lo, hi]}


PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


def dims(*sizes) -> str:
    """A shape as the trace writes it inside an operand's type: `[8,4096,16,128]`."""
    return "[" + ",".join(str(int(s)) for s in sizes) + "]"


def pallas_seconds(reduced: dict, has: str = "", lacks: str = "") -> float:
    """Device seconds of the Pallas kernels (the `tpu_custom_call`s) whose
    instruction text holds `has` and does not hold `lacks`. A device event is
    named by its whole HLO instruction, operand types included, and NOT by the
    kernel function (a kernel shows as `%fn.24` or `%jvp__.7`): until the
    program gives its kernels stable names, a kernel is told by an operand
    only it has."""
    return sum(v for n, v in reduced["op_s"].items()
               if PALLAS_CALL in n and has in n and not (lacks and lacks in n))


def _main_module(reduced: dict) -> str | None:
    """The program that took most device time in the slice: the train step."""
    return max(reduced["module_s"], key=reduced["module_s"].get) if reduced["module_s"] else None


def main_module_runs(reduced: dict) -> float:
    """The steps of a traced training slice, a device: the seconds of the
    step program as far as it ran INSIDE the slice, over the median length of
    its runs that the slice's ends did not cut. A kernel's seconds are
    clipped the same way, so a share that multiplies by this compares like
    with like. With no whole run in the slice: over its longest part there,
    an upper end that `steps_measured` does not pass on."""
    main = _main_module(reduced)
    if main is None:
        return 0.0
    whole = reduced["module_whole_s"].get(main)
    one = statistics.median(whole) if whole else reduced["module_longest_s"][main]
    return reduced["module_s"][main] / one


def steps_measured(reduced: dict) -> float | None:
    """`main_module_runs` where the slice holds a whole step to measure the
    cut ones by; None otherwise, and a reader then leaves its metric out."""
    if not reduced["module_whole_s"].get(_main_module(reduced)):
        return None
    return main_module_runs(reduced)


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    events = load_xplane(max(paths, key=os.path.getmtime))
    out = reduce_events(events, n_devices)
    out["lines"] = events["lines"]
    out["events"] = events
    return out
