"""Scalar/histogram experiment logging — the VisualDL analog.

Reference context: paddle ships VisualDL (`visualdl.LogWriter`) as its
observability surface (SURVEY §5 metrics/logging). Zero-dependency
TPU-native stand-in: an append-only JSONL event log per run directory with
the same add_scalar/add_histogram/add_text writer API, a reader for
programmatic analysis, and a hapi/Engine callback that streams training
metrics into it. Files are plain JSONL — greppable, diffable, and loadable
into any dashboard.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
import weakref

import numpy as np

__all__ = ["LogWriter", "LogReader", "VisualDLCallback"]

# durability: every live writer flushes at interpreter exit, so a
# short-lived run (a short script, a crashed one) never drops the tail of
# its buffered JSONL events. Weak set — registration must not keep
# writers (and their open files) alive.
_LIVE_WRITERS: "weakref.WeakSet[LogWriter]" = weakref.WeakSet()
_atexit_lock = threading.Lock()
_atexit_installed = False


def _flush_live_writers():
    for w in list(_LIVE_WRITERS):
        try:
            w.flush()
        except (OSError, ValueError):
            continue  # a closed/broken file at exit is not worth a raise


def _register_for_atexit(writer: "LogWriter"):
    global _atexit_installed
    with _atexit_lock:
        if not _atexit_installed:
            atexit.register(_flush_live_writers)
            _atexit_installed = True
        _LIVE_WRITERS.add(writer)


class LogWriter:
    """visualdl.LogWriter API over JSONL (one event per line)."""

    def __init__(self, logdir="./runs", max_queue=100, flush_secs=10,
                 file_name=""):
        os.makedirs(logdir, exist_ok=True)
        self.logdir = logdir
        name = file_name or f"events.{int(time.time())}.jsonl"
        self._path = os.path.join(logdir, name)
        self._f = open(self._path, "a")
        self._since_flush = 0
        self._max_queue = max_queue
        self._flush_secs = flush_secs
        self._last_flush = time.time()
        _register_for_atexit(self)

    def _emit(self, record: dict):
        record["wall_time"] = time.time()
        self._f.write(json.dumps(record) + "\n")
        self._since_flush += 1
        if (self._since_flush >= self._max_queue
                or time.time() - self._last_flush >= self._flush_secs):
            self.flush()

    def add_scalar(self, tag: str, value, step: int = 0):
        self._emit({"kind": "scalar", "tag": tag, "value": float(value),
                    "step": int(step)})

    def add_scalars(self, main_tag: str, tag_value_dict: dict, step: int = 0):
        for k, v in tag_value_dict.items():
            self.add_scalar(f"{main_tag}/{k}", v, step)

    def add_histogram(self, tag: str, values, step: int = 0, buckets: int = 10):
        arr = np.asarray(values, np.float64).ravel()
        hist, edges = np.histogram(arr, bins=buckets)
        self._emit({"kind": "histogram", "tag": tag, "step": int(step),
                    "hist": hist.tolist(), "edges": edges.tolist(),
                    "min": float(arr.min()) if arr.size else 0.0,
                    "max": float(arr.max()) if arr.size else 0.0,
                    "mean": float(arr.mean()) if arr.size else 0.0})

    def add_text(self, tag: str, text: str, step: int = 0):
        self._emit({"kind": "text", "tag": tag, "text": str(text),
                    "step": int(step)})

    def flush(self):
        if not self._f.closed:
            self._f.flush()
        self._since_flush = 0
        self._last_flush = time.time()

    def close(self):
        if not self._f.closed:
            self.flush()
            self._f.close()
        _LIVE_WRITERS.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class LogReader:
    """Read back a run directory's events for analysis/regression checks."""

    def __init__(self, logdir):
        self.logdir = logdir

    def _events(self):
        for name in sorted(os.listdir(self.logdir)):
            if not name.endswith(".jsonl"):
                continue
            with open(os.path.join(self.logdir, name)) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        yield json.loads(line)

    def tags(self):
        return sorted({e["tag"] for e in self._events()})

    def scalars(self, tag: str):
        """[(step, value)] for a scalar tag, step-ordered."""
        out = [(e["step"], e["value"]) for e in self._events()
               if e["kind"] == "scalar" and e["tag"] == tag]
        return sorted(out)

    def last(self, tag: str):
        """The highest-step (step, value) of a scalar tag, or None."""
        series = self.scalars(tag)
        return series[-1] if series else None

    def texts(self, tag: str):
        """[(step, text)] for a text tag, step-ordered (e.g. the metrics
        registry's histogram exports)."""
        out = [(e["step"], e["text"]) for e in self._events()
               if e["kind"] == "text" and e["tag"] == tag]
        return sorted(out)


class VisualDLCallback:
    """hapi callback streaming per-step train scalars, per-epoch metrics and
    eval scalars into a LogWriter (reference hapi/callbacks.py VisualDL).
    Standalone (duck-typed) so this module never imports hapi — hapi
    re-exports it; every hook the fit loop calls exists."""

    def __init__(self, logdir="./runs", tag_prefix="train", log_dir=None):
        self.writer = LogWriter(log_dir or logdir)
        self.prefix = tag_prefix
        self._step = 0

    @staticmethod
    def _num(v):
        v = v[0] if isinstance(v, (list, tuple)) else v
        return float(v) if isinstance(v, (int, float)) else None

    def on_epoch_begin(self, epoch, logs=None):
        self._epoch = epoch

    def on_train_batch_end(self, step, logs=None):
        for k, v in (logs or {}).items():
            vv = self._num(v)
            if vv is not None:
                self.writer.add_scalar(f"{self.prefix}/{k}", vv, self._step)
        self._step += 1

    def on_epoch_end(self, epoch, logs=None):
        for k, v in (logs or {}).items():
            vv = self._num(v)
            if vv is not None:
                self.writer.add_scalar(f"{self.prefix}/{k}", vv, epoch)
        self.writer.flush()

    def on_eval_end(self, logs=None):
        for k, v in (logs or {}).items():
            vv = self._num(v)
            if vv is not None:
                self.writer.add_scalar(f"eval/{k}", vv, self._step)
        self.writer.flush()

    def on_train_end(self, logs=None):
        self.writer.close()

    # duck-typed remainder of the hapi Callback protocol
    def set_params(self, params):
        self.params = params

    def set_model(self, model):
        self.model = model

    def on_train_begin(self, logs=None):
        pass

    def on_eval_begin(self, logs=None):
        pass

    def on_train_batch_begin(self, step, logs=None):
        pass
