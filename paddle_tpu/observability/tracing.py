"""The program's one host-span primitive, with trace-id propagation.

A span is recorded two ways at once, and costs next to nothing when neither
is on:

  * into the in-memory list of a **collection window**
    (`start_tracing()` / `stop_tracing()`), as a Chrome `X` (complete) event
    with `args = {trace_id, component, parent, **attrs}` — what
    `export_chrome` writes and the benchmark's serving kind reads;
  * onto the **profiler's clock**: every span enters a
    `jax.profiler.TraceAnnotation(name)` while a `jax.profiler` session runs,
    so it is an event of `/host:CPU` in the same xplane as the device's
    `XLA Ops` and an idle gap of the device can be named by what the host was
    doing. No call of this module is needed for that: a session is enough.
    (`jax` is looked up only if something else already imported it — the
    module itself stays dependency-free host code, importable from the
    scheduler/router hot paths.)

Contract:

  * `span(name, component=..., trace_id=..., **attrs)` is the context
    manager; `trace_id=None` inherits the thread's current trace context.
    A recorded span names the span that encloses it on its thread
    (`args.parent`), so a layer's self time is its span less its children;
  * `record_span(name, begin_ns, dur_ns, args)` records a span measured by
    the caller (a queue wait known only at its end). Under a profiler
    session it leaves a zero-length annotation carrying `dur_us`, since a
    past interval cannot be entered;
  * `tracing_active()` is true inside a collection window OR a profiler
    session: call sites guard attribute lists (`trace_ids=[...]`) with it;
  * `trace_context(trace_id)` sets the thread-local context — a worker
    picking up request R wraps its work in `trace_context(R.trace_id)` and
    every span inside lands correlated. The router mints one id per request;
    it rides the payload / the Request object through replica -> engine ->
    scheduler -> decode step;
  * `profiler.RecordEvent` and `profiler.Profiler` are thin wrappers over
    this module: there is ONE store of host spans;
  * `export_chrome(path)` writes one ``{"traceEvents": [...]}`` JSON of the
    collected spans (host clock). Host spans and device activity on one
    timeline come from the profiler's own xplane, where the spans already
    are.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import uuid

__all__ = ["start_tracing", "stop_tracing", "tracing_active", "collecting",
           "span", "trace_context", "current_trace_id", "new_trace_id",
           "record_span", "export_chrome", "events_snapshot"]

_ACTIVE = False
_lock = threading.Lock()
_events: list[dict] = []
_MAX_EVENTS = 1_000_000  # hard cap: tracing must never OOM the host
_tls = threading.local()
# os.getpid() is a SYSCALL per call (tens of µs under gVisor-class
# sandboxes) — cache it; a fork gets a fresh module state anyway under
# the spawn start-method every paddle_tpu multiproc path uses
_PID = os.getpid()
_annotation = None   # jax.profiler.TraceAnnotation, once jax is imported
_SCALARS = (str, int, float)


def _session():
    """`jax.profiler.TraceAnnotation` while a profiler session is running,
    else None. Never imports jax: without it there is no session."""
    global _annotation
    ann = _annotation
    if ann is None:
        mod = sys.modules.get("jax.profiler")
        ann = _annotation = getattr(mod, "TraceAnnotation", None)
        if ann is None:
            return None
    return ann if ann.is_enabled() else None


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def tracing_active() -> bool:
    """True when a span would be recorded anywhere: in a collection window
    or under a profiler session."""
    return _ACTIVE or _session() is not None


def start_tracing():
    """Begin a collection window (clears previously collected spans)."""
    global _ACTIVE
    with _lock:
        _events.clear()
    _ACTIVE = True


def stop_tracing() -> list:
    """End the window; returns the collected Chrome events."""
    global _ACTIVE
    _ACTIVE = False
    with _lock:
        return list(_events)


def collecting() -> bool:
    """True inside a collection window (the in-memory list is filling)."""
    return _ACTIVE


def events_snapshot(since: int = 0) -> list:
    with _lock:
        return _events[since:]


def reset():
    """Stop collection AND drop collected events (test isolation —
    stop_tracing alone keeps them for export)."""
    global _ACTIVE
    _ACTIVE = False
    with _lock:
        _events.clear()


def current_trace_id() -> str | None:
    return getattr(_tls, "trace_id", None)


@contextlib.contextmanager
def trace_context(trace_id: str | None):
    """Bind `trace_id` as this thread's current trace — spans inside
    inherit it. None is a no-op bind."""
    prev = getattr(_tls, "trace_id", None)
    _tls.trace_id = trace_id if trace_id is not None else prev
    try:
        yield
    finally:
        _tls.trace_id = prev


def _store(name: str, begin_ns: int, dur_ns: int, args: dict):
    """One Chrome complete event into the window's list; the thread's
    current trace id is attached when the caller set none."""
    if "trace_id" not in args:
        tid = getattr(_tls, "trace_id", None)
        if tid is not None:
            args["trace_id"] = tid
    ev = {"name": name, "ph": "X", "ts": begin_ns / 1e3,
          "dur": dur_ns / 1e3, "pid": _PID,
          "tid": threading.get_ident(), "args": args}
    with _lock:
        if len(_events) < _MAX_EVENTS:
            _events.append(ev)


def _scalars(args: dict) -> dict:
    """What a TraceAnnotation can carry as stats: lists (`trace_ids`) stay
    in the window's list only."""
    return {k: v for k, v in args.items() if isinstance(v, _SCALARS)}


def record_span(name: str, begin_ns: int, dur_ns: int,
                args: dict | None = None):
    """A span the caller measured itself (`time.perf_counter_ns()` at its
    begin, its length), recorded at its END."""
    ann = _session()
    if ann is not None:
        with ann(name, dur_us=dur_ns // 1000, **_scalars(args or {})):
            pass
    if _ACTIVE:
        args = dict(args) if args else {}
        parent = getattr(_tls, "span", None)
        if parent is not None:
            args.setdefault("parent", parent)
        _store(name, begin_ns, dur_ns, args)


class span:
    """Context manager recording one span (module docstring). With
    `bind=True` (default) the span also binds its trace id, and its own name
    as the enclosing span, as the thread context for its duration, so nested
    spans correlate. Pass `bind=False` when the span wraps a GENERATOR's
    lifetime (e.g. the router's per-request stream): a suspended
    generator's `with` stays entered across unrelated work on the
    consumer thread, and interleaved generators would restore the
    thread-local non-LIFO — the span still CARRIES the id, it just must
    not own the thread context."""

    __slots__ = ("name", "component", "trace_id", "attrs", "bind",
                 "_begin", "_prev", "_parent", "_ann")

    def __init__(self, name: str, component: str = "",
                 trace_id: str | None = None, bind: bool = True, **attrs):
        self.name = name
        self.component = component
        self.trace_id = trace_id
        self.bind = bind
        self.attrs = attrs
        self._begin = None
        self._prev = None
        self._parent = None
        self._ann = None

    def _args(self) -> dict:
        args = dict(self.attrs)
        if self.component:
            args["component"] = self.component
        if self.trace_id is not None:
            args["trace_id"] = self.trace_id
        return args

    def __enter__(self):
        ann = _session()
        if ann is not None:
            self._ann = ann(self.name, **_scalars(self._args()))
            self._ann.__enter__()
        if _ACTIVE:
            self._begin = time.perf_counter_ns()
            self._parent = getattr(_tls, "span", None)
            if self.bind:
                _tls.span = self.name
                if self.trace_id is not None:
                    self._prev = getattr(_tls, "trace_id", None)
                    _tls.trace_id = self.trace_id
        return self

    def __exit__(self, *a):
        if self._ann is not None:
            self._ann.__exit__(*a)
            self._ann = None
        if self._begin is not None:
            dur = time.perf_counter_ns() - self._begin
            args = self._args()
            if self.bind:
                _tls.span = self._parent
                if self.trace_id is not None:
                    _tls.trace_id = self._prev
            if self._parent is not None:
                args["parent"] = self._parent
            if _ACTIVE:
                _store(self.name, self._begin, dur, args)
            self._begin = None
        return False


def export_chrome(path: str, extra_events: list | None = None) -> dict:
    """Write the collected spans (plus caller-supplied events) as ONE Chrome
    trace file on the host's clock. Returns {host_events, path}."""
    events = events_snapshot()
    n_host = len(events)
    if extra_events:
        events.extend(extra_events)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return {"host_events": n_host, "path": path}
