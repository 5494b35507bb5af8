"""From the profiler's xplane to numbers: device busy time, time by device
operation, collectives and their exposed part, the longest idle gaps named
by what the host was doing.

Two stages, so that the arithmetic can be checked on a small recorded trace
(`tests/data/`): `load_xplane` turns the file into plain lists of
[name, start_ns, duration_ns]; `reduce_events` turns those into numbers.

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

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("train.", "engine.", "loadgen.", "bench.")
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


def reduce_events(events: dict, n_devices: int | None = None) -> dict:
    """Numbers of one traced window, seconds throughout, averaged over the
    device planes: busy_s; op_s {operation name: seconds}; collective_s and
    exposed_collective_s; device_ops and idle_gaps as the result line's
    `breakdown` wants them."""
    planes = sorted(events["devices"])
    if n_devices:
        planes = planes[:n_devices]
    if not planes:
        return {"busy_s": 0.0, "op_s": {}, "op_n": {}, "module_s": {}, "module_n": {},
                "collective_s": 0.0,
                "exposed_collective_s": 0.0, "device_ops": [], "idle_gaps": [],
                "planes": []}
    busy = coll = exposed = 0.0
    op_s: dict[str, float] = {}
    op_n: dict[str, float] = {}
    module_s: dict[str, float] = {}
    module_n: dict[str, float] = {}
    first_busy = None
    for plane in planes:
        ops = events["devices"][plane]["ops"]
        compute = _union([(s, s + d) for n, s, d in ops if not is_collective(n)])
        comm = _union([(s, s + d) for n, s, d in ops if is_collective(n)])
        both = _union(compute + comm)
        busy += _length(both)
        coll += _length(comm)
        exposed += _minus(comm, compute)
        for n, _, d in ops:
            op_s[n] = op_s.get(n, 0.0) + d
            op_n[n] = op_n.get(n, 0.0) + 1.0 / len(planes)
        for n, _, d in events["devices"][plane].get("modules", []):
            module_s[n] = module_s.get(n, 0.0) + d
            module_n[n] = module_n.get(n, 0.0) + 1.0 / len(planes)
        if first_busy is None:
            first_busy = both
    k = len(planes) * 1e9
    # idle gaps of the first device, named by the host span they fall in
    host = sorted(events["host"], key=lambda e: e[2])   # shortest last wins
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(first_busy, first_busy[1:])),
                  reverse=True)[:10]
    idle = []
    for length, lo, hi in gaps:
        mid = (lo + hi) / 2
        inside = [n for n, s, d in host if s <= mid <= s + d]
        idle.append([inside[0] if inside else "host:unnamed", length / 1e9])
    # a device event is named by its whole HLO instruction: keep its head
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    top = [(n.split(" custom_call_target")[0][:160] + (" <pallas>" if PALLAS_CALL in n else ""), v)
           for n, v in top]
    return {"busy_s": busy / k, "op_s": {n: v / k for n, v in op_s.items()},
            "op_n": op_n, "module_s": {n: v / k for n, v in module_s.items()},
            "module_n": module_n,
            "collective_s": coll / k, "exposed_collective_s": exposed / k,
            "device_ops": [[n, v / k] for n, v in top], "idle_gaps": idle,
            "planes": planes}


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


def main_module_runs(reduced: dict) -> float:
    """How often the program that took most device time ran, a device: the
    steps of a traced training window."""
    if not reduced["module_s"]:
        return 0.0
    return reduced["module_n"][max(reduced["module_s"], key=reduced["module_s"].get)]


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    events = load_xplane(max(paths, key=os.path.getmtime))
    out = reduce_events(events, n_devices)
    out["lines"] = events["lines"]
    out["events"] = events
    return out
