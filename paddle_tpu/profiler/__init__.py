"""Profiler (reference: python/paddle/profiler/profiler.py:346 + C++ profiler
paddle/fluid/platform/profiler/profiler.h:47).

TPU-native: the paddle-shaped face of `paddle_tpu.observability.tracing`,
the program's ONE store of host spans (the HostTracer analog), plus
`device_trace`, a `jax.profiler` session (XLA/xplane, viewable in
TensorBoard/xprof — the CudaTracer/CUPTI analog) in which every span is a
`TraceAnnotation` on the device trace's own clock.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from enum import Enum
from typing import Callable

from paddle_tpu.observability import tracing as _tracing

__all__ = [
    "Profiler", "ProfilerTarget", "RecordEvent", "make_scheduler",
    "export_chrome_tracing", "SummaryView",
]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


class RecordEvent:
    """Host event annotation (reference: platform/profiler/event_tracing.h):
    `tracing.span` under paddle's name, with explicit `begin()` / `end()`.
    It lands in the active collection window carrying the thread's trace id
    and, under a profiler session, in the xplane."""

    def __init__(self, name: str, event_type=None, attrs: dict | None = None):
        self.name = name
        self._span = _tracing.span(name)
        if attrs:
            self._span.attrs = dict(attrs)

    def begin(self):
        self._span.__enter__()

    def end(self):
        self._span.__exit__(None, None, None)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()
        return False


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0, skip_first: int = 0):
    total = closed + ready + record

    def sched(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return sched


def export_chrome_tracing(dir_name: str, worker_name: str | None = None) -> Callable:
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{int(time.time())}.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": prof._events}, f)
        prof._export_path = path

    return handler


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._events = []
        self._export_path = None
        self._jax_trace_dir = None

    def start(self):
        # a window of the one store: opened here unless one is open already
        # (then this profiler reads its slice of it and leaves it open)
        self._opened = not _tracing.collecting()
        if self._opened:
            _tracing.start_tracing()
        self._mark = 0 if self._opened else len(_tracing.events_snapshot())

    def stop(self):
        self._events = _tracing.events_snapshot(self._mark)
        if self._opened:
            _tracing.stop_tracing()
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        self._step += 1

    def step_info(self, unit=None):
        return f"step {self._step}"

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms", views=None):
        by_name: dict[str, float] = {}
        for e in self._events:
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
        lines = ["name\ttotal_us"] + [f"{k}\t{v:.1f}" for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])]
        return "\n".join(lines)

    def export(self, path: str, format: str = "json"):
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events}, f)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False


@contextlib.contextmanager
def device_trace(log_dir: str):
    """XLA device tracing via jax.profiler (xplane; the CUPTI-tracer analog)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
