"""What every kind of cell shares: the files of a cell, the device, the
compile meter, the profiler window, the result line.

A cell is `workloads/<cell>.json`; it names `configs/<config>.json` and
`traffic/<traffic>.json`. A per-layer metric is `metrics/<name>.py` with one
function `read(run) -> float | None`. Nothing here names a cell, a
configuration, a mix or a metric: adding one adds files, and an entry in
BENCHMARK.json.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from benchmark import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool = False) -> dict:
    """The cell's file with its configuration and traffic mix resolved. A
    rehearsal keeps the cell's structure and swaps every size for the tiny
    ones of `rehearsal.json`."""
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["model"] = load_json("configs", f"{cell['config']}.json")
    cell["mix"] = load_json("traffic", f"{cell['traffic']}.json")
    if rehearsal:
        tiny = load_json("rehearsal.json")
        cell["model"].update(tiny["model"])
        cell["mix"].update(tiny["mix"][cell["kind"]])
        cell.update(tiny["cell"][cell["kind"]])
    return cell


def cell_metrics(manifest: dict, cell: str, group: str) -> list[dict]:
    """The metrics of `group` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def llama_config(model: dict, **extra):
    """The program's config object, filled by key from the configuration's
    file. Keys the program does not know (the source, the assumptions) stay
    in the file."""
    import dataclasses

    from paddle_tpu.models.llama import LlamaConfig

    known = {f.name for f in dataclasses.fields(LlamaConfig)}
    return LlamaConfig(**{k: v for k, v in model.items() if k in known}, **extra)


class NoChip(Exception):
    pass


def open_device(chips: int, rehearsal: bool) -> dict:
    """The devices this run uses, and JAX's compile cache. Without the chips
    the cell asks for there is no run: never a fallback to the CPU."""
    import jax

    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            raise NoChip(f"the rehearsal of a {chips}-chip cell needs {chips} "
                         f"devices (XLA_FLAGS=--xla_force_host_platform_device_count={chips})")
    else:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no accelerator (platform {devices[0].platform})")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
        import paddle_tpu as paddle
        from paddle_tpu.core.compile_cache import enable_compile_cache

        paddle.set_device("tpu")
        enable_compile_cache()
        # small programs are cached too: set-up is then the same in every run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "used": devices[:chips]}


def log(msg: str):
    """Progress, on stderr: what the window measured is on record before the
    reference runs."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


class CompileMeter:
    """JAX's own account of compiling, via jax.monitoring (copied from
    chip_smoke.py): programs traced, seconds spent tracing, lowering and
    compiling or fetching from the cache, cache hits and misses."""

    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _DURATIONS = (_TRACE,
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.secs = 0.0
        self.traces = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.secs += secs
            self.traces += event == self._TRACE

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Profile:
    """The profiler over one slice of the measured window: from 40% of it,
    for `trace_s` seconds at most. `poll(now)` is called from the loop that
    drives the window; the xplane is reduced once the window has closed.

    The slice is what lies inside the `bench.slice` span, opened once the
    profiler runs and closed when `poll` decides to stop, BEFORE the profiler
    is stopped: its two ends are on the trace's own clock, and `reduce.py`
    clips every device event to them. The host's clock keeps `t_begin` and
    `host_window_s` for the readers that compare the slice with host-clock
    records of the program; nothing divides by them."""

    def __init__(self, on: bool, t0: float, seconds: float, trace_s: float):
        self.on = on
        self.begin = t0 + 0.4 * seconds
        self.end = self.begin + min(trace_s, 0.5 * seconds)
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.state = "before" if on else "done"
        self.host_window_s = None
        self.t_begin = None

    def poll(self, now: float):
        import jax

        if self.state == "before" and now >= self.begin:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._slice = annotate(reduce.SLICE_SPAN)
            self._slice.__enter__()
            self.t_begin = time.perf_counter()
            self.state = "running"
        elif self.state == "running" and now >= self.end:
            self._slice.__exit__(None, None, None)
            self.host_window_s = time.perf_counter() - self.t_begin
            self.state = "stopping"
            # collecting a trace takes seconds: off the thread that offers load
            self._stopper = threading.Thread(target=jax.profiler.stop_trace,
                                             name="bench.stop_trace")
            self._stopper.start()

    def stop(self):
        """End the slice if it is still open, and wait until the trace is
        written."""
        if self.state == "running":
            self.poll(float("inf"))
        if self.state == "stopping":
            self._stopper.join()
            self.state = "done"

    def reduce(self, n_devices: int):
        """The reduced trace (see reduce.py), or None without one."""
        if self.host_window_s is None:
            return None
        try:
            return reduce.reduce_dir(self.dir, n_devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    """A span on the profiler's own clock, so that an idle gap of the device
    can be named by what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_per_layer(names: list[str], run: dict) -> dict:
    """Each per-layer metric through its own reader. A reader that finds
    nothing to read returns None and the metric is left out."""
    from benchmark import roofline

    out = {}
    for name in names:
        path = os.path.join(HERE, "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"benchmark_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        try:
            value = mod.read(run)
        except roofline.UnknownDevice:
            if not run.get("rehearsal"):
                raise
            value = None    # a rehearsal's CPU has no peak and reports no share of one
        if value is not None:
            out[name] = float(value)
    return out


def emit(result: dict, checks: dict):
    """Every number compared beside its limit as the last lines of stderr,
    then the one result object as the last line of stdout, `checks` last."""
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    print(json.dumps(result), flush=True)


def with_units(values: dict, metrics: list[dict]) -> dict:
    units = {m["name"]: m["unit"] for m in metrics}
    return {k: {"value": v, "unit": units[k]} for k, v in values.items() if k in units}


@contextlib.contextmanager
def interpret_kernels(on: bool):
    """A CPU rehearsal reaches the Pallas kernels the only way a CPU can."""
    if not on:
        yield
        return
    from paddle_tpu.ops.pallas import flash_attention, paged_attention

    with flash_attention.force_interpret(), paged_attention.force_interpret():
        yield


def dump_trace(path: str, reduced: dict, keep_s: float | None = 0.7):
    """For looking at a trace by hand and for recording the small trace the
    tests keep: plane and line names, and the events that start in the first
    `keep_s` seconds after the first device operation; with `keep_s` None
    (`BENCH_DUMP_TRACE_S=all`) every event, the `bench.slice` span with them:
    a whole slice as `reduce_events` takes it."""
    import gzip

    events = reduced["events"]
    starts = [e[1] for d in events["devices"].values() for e in d["ops"]]
    lo = min(starts) if starts else 0.0
    lo, hi = (lo, lo + keep_s * 1e9) if keep_s is not None else (float("-inf"), float("inf"))

    def cut(evs):
        return [e for e in evs if lo <= e[1] < hi]

    small = {"lines": reduced["lines"], "host": cut(events["host"]),
             "devices": {p: {k: cut(v) for k, v in d.items()}
                         for p, d in events["devices"].items()}}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def seeded_model(model_cfg: dict, seed: int):
    """The program's model as a user builds it, in the configuration's type,
    with the seed's weights in place of its own: made on the device in one
    jitted call that takes over the memory of the model's initial values."""
    from benchmark import weights
    from paddle_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(llama_config(model_cfg))
    model.to(dtype=model_cfg["dtype"])
    params = model.parameters()
    specs = weights.leaf_specs(model_cfg)
    if [tuple(p.shape) for p in params] != [s[1] for s in specs]:
        raise ValueError("the program's parameters are not the leaves weights.py makes")
    made = weights.make_all(seed, specs, model_cfg["dtype"],
                            donate=[p._value for p in params])
    for p, v in zip(params, made):
        p._set_value(v)
    return model
