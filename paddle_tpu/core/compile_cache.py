"""Where JAX's persistent compilation cache lives for the on-chip entry points.

A chip machine is handed out fresh per call, and a 7B-width train step plus
the serving engine's decode/prefill programs take minutes to compile cold, so
every entry point that runs on a chip (`chip_smoke.py`, `benchmark/run.py`,
the `inference/serve.py` CLI) calls `enable_compile_cache()` once, before
its first compile.

The cache is placed from OUTSIDE the program: when `JAX_COMPILATION_CACHE_DIR`
is set JAX reads it by itself and nothing is set in code; otherwise the cache
goes to one fixed directory inside the checkout — never a temp name, pid or
timestamp, because the path is part of the cache key and a directory that
moves never hits. Deliberately NOT called at `import paddle_tpu` or from
`tests/conftest.py`: CPU executables cached in a sandbox would travel with
the tree to a machine with other CPU features.

This module also keeps the repo's ONE account of compiling: the compile log,
a `jax.monitoring` listener that `enable_compile_cache()` registers (or
`start_compile_log()` alone, where no cache is wanted). It holds one entry
for every program JAX compiled: its `fun_name`, when it started, the seconds
spent tracing, lowering and backend-compiling (or fetching from the
persistent cache), and whether the cache was asked, hit or missed — so a
set-up that is slow can say WHICH program missed. The process metrics
registry reads the same log at scrape time (`compile_cache_hits_total`,
`compile_cache_misses_total`).

Stdlib + jax only, and loadable by file path: the standalone serving CLI
loads it without the `paddle_tpu` package (no registry there, no series).
"""
from __future__ import annotations

import os
import sys
import threading
import time

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache",
           "start_compile_log", "compile_log", "compile_totals"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache` (git-ignored), next to the `paddle_tpu`
    package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile of the process."""
    start_compile_log()
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# the compile log
# ---------------------------------------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_PHASE = {_TRACE: "trace_s", _LOWER: "lower_s", _COMPILE: "compile_s"}
_CACHE = "/jax/compilation_cache/"

_log: list[dict] = []
_log_lock = threading.Lock()
_traces = 0
_listening = False
_tls = threading.local()


def _pending() -> dict:
    """This thread's program in the making: JAX traces, lowers and compiles
    one program on one thread, each phase opened by a scalar that carries
    `fun_name` and closed by its duration."""
    p = getattr(_tls, "pending", None)
    if p is None:
        p = _tls.pending = {"t0": None, "trace_s": 0.0, "lower_s": 0.0,
                            "depth": 0, "cache": "off"}
    return p


def _on_scalar(event, value, **kw):
    if event not in _PHASE:
        return
    p = _pending()
    if event == _TRACE and p["depth"] == 0:
        # an outermost trace opens the next program: what a trace left that
        # lowered nothing (a lookup in JAX's trace cache, which reports a
        # trace too: a step lowered before its first call) is no part of it
        _tls.pending = None
        p = _pending()
    if p["t0"] is None:
        p["t0"] = time.perf_counter()
    # a jitted function called while another is traced is traced inside it:
    # only the outermost phase's seconds count
    p["depth"] += 1


def _on_duration(event, secs, **kw):
    global _traces
    key = _PHASE.get(event)
    if key is None:
        return
    p = _pending()
    if p["depth"] == 0:
        # the phase was opened before the log started listening
        p["t0"] = time.perf_counter() - secs
    else:
        p["depth"] -= 1
    if event == _TRACE:
        with _log_lock:
            _traces += 1
    if p["depth"] > 0:
        return
    if event != _COMPILE:
        p[key] += secs
        return
    entry = {"fun_name": kw.get("fun_name", ""), "t0": p["t0"],
             "trace_s": p["trace_s"], "lower_s": p["lower_s"],
             "compile_s": secs, "cache": p["cache"]}
    _tls.pending = None
    with _log_lock:
        _log.append(entry)


def _on_event(event, **kw):
    # between the scalar that opens the backend compile and the duration
    # that closes it, on the compiling thread
    if not event.startswith(_CACHE):
        return
    p = _pending()
    what = event[len(_CACHE):]
    if what == "compile_requests_use_cache":
        p["cache"] = "unstored"   # asked; what follows says how it ended
    elif what == "cache_hits":
        p["cache"] = "hit"
    elif what == "cache_misses":
        p["cache"] = "miss"       # JAX's "miss": compiled AND written back


def _collect(reg):
    t = compile_totals()
    reg.counter("compile_cache_hits_total",
                "programs fetched from JAX's persistent compilation cache"
                ).labels()._set_total(float(t["hits"]))
    reg.counter("compile_cache_misses_total",
                "programs compiled and written to JAX's persistent "
                "compilation cache").labels()._set_total(float(t["misses"]))


def start_compile_log():
    """Register the listeners, once a process. `enable_compile_cache()` does
    it; call it alone to account for compiling without a persistent cache."""
    global _listening
    # every call: `registry().reset()` drops collectors. `import paddle_tpu`
    # loads the registry; the standalone CLI has none and must not import
    # the package to get one
    metrics = sys.modules.get("paddle_tpu.observability.metrics")
    if metrics is not None:
        metrics.registry().ensure_collector(_collect)
    with _log_lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring as monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_log() -> list[dict]:
    """One entry a compiled program, in order of completion: `fun_name`
    (as JAX names the program: `jit(<function>)`), `t0`
    (`time.perf_counter()` when its first phase began), `trace_s` /
    `lower_s` (seconds of tracing and lowering on that thread since the
    program before it), `compile_s` (the backend compile, or the fetch from
    the persistent cache) and `cache`:
    'hit'; 'miss' (compiled and written to the cache, JAX's own meaning);
    'unstored' (asked and compiled, but under the thresholds for writing:
    it will be compiled again next time); 'off' (the cache was not asked).
    Empty until `start_compile_log()`."""
    with _log_lock:
        return [dict(e) for e in _log]


def compile_totals(prefix: str = "") -> dict:
    """Sums over the entries whose `fun_name` starts with `prefix`:
    programs, seconds (all three phases), cache hits, misses and unstored;
    with no prefix also `traces`, every trace JAX made (a program is traced
    once, a retrace shows here)."""
    entries = [e for e in compile_log() if e["fun_name"].startswith(prefix)]
    out = {"programs": len(entries),
           "secs": sum(e["trace_s"] + e["lower_s"] + e["compile_s"]
                       for e in entries),
           "hits": sum(e["cache"] == "hit" for e in entries),
           "misses": sum(e["cache"] == "miss" for e in entries),
           "unstored": sum(e["cache"] == "unstored" for e in entries)}
    if not prefix:
        out["traces"] = _traces
    return out
