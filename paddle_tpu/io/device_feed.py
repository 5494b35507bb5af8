"""Asynchronous device feed: double-buffered host->device prefetch + bounded
async step dispatch.

Reference analog: the buffered reader + async executor pair that keeps the
device busy between steps (reference reader.py's buffered decorator feeding
the StandaloneExecutor). TPU-native restatement of the tf.data
"prefetch-to-device" idiom: JAX already dispatches the compiled step
asynchronously, so the only things that can serialize a training loop are
  1. host work on the critical path — fetch, transform, collate, and the
     per-input `jax.device_put` that `CompiledTrainStep.__call__` used to
     redo (spec trimming included) for every batch, and
  2. a device->host sync per step — every `float(loss)` blocks until the
     step finishes, collapsing the run-ahead window to zero.
This module removes both:
  * `DeviceFeeder` / `prefetch_to_device` run fetch+collate+sharded placement
    on a background thread with a bounded in-flight queue (depth batches of
    HBM, the double-buffer), propagating worker exceptions to the consumer
    and joining the thread on close;
  * `BatchSpecCache` computes the per-dim divisibility-trimmed
    `NamedSharding` for each input ONCE per batch signature (shapes+dtypes),
    not per step;
  * `DispatchWindow` bounds run-ahead to ~2 steps in flight (blocking on the
    loss of step N-w before admitting step N), so async dispatch cannot pile
    un-executed programs' batches up in HBM;
  * `LossFuture` defers the device->host loss read so callers fetch metrics
    every k steps (`FLAGS_metrics_sync_every`) instead of every step.
"""
from __future__ import annotations

import collections
import queue
import threading
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.observability import tracing as obs_tracing

__all__ = ["DeviceFeeder", "FeederWorkerError", "prefetch_to_device",
           "BatchSpecCache", "LossFuture", "DispatchWindow",
           "default_batch_spec", "trim_batch_spec"]

faults.register(
    "feeder.collate",
    "DeviceFeeder worker crash during fetch/collate of the next batch "
    "(a dataset/transform bug or a dying storage mount)")
faults.register(
    "feeder.device_put",
    "DeviceFeeder worker crash during the sharded host->device placement "
    "of a collated batch")

# thread-name prefix shared by every io/reader background thread: the test
# suite's thread-hygiene guard keys on it to detect leaked prefetchers
THREAD_PREFIX = "paddle_tpu.io"


def interruptible_put(q: queue.Queue, item, stop: threading.Event,
                      poll: float = 0.05) -> bool:
    """Bounded put that stays interruptible: a producer blocked on a full
    queue re-checks `stop` every `poll` seconds, so an abandoned consumer's
    close() unblocks it instead of stranding the thread. Shared by
    DeviceFeeder, the DataLoader thread prefetcher, and reader.buffered."""
    while not stop.is_set():
        try:
            q.put(item, timeout=poll)
            return True
        except queue.Full:
            continue
    return False


def stop_and_join(q: queue.Queue, stop: threading.Event,
                  thread: threading.Thread, timeout: float = 5.0):
    """Producer-thread teardown: signal stop, drain the queue so a blocked
    put wakes, then JOIN the thread (the no-leaked-prefetchers contract the
    conftest thread-hygiene guard enforces)."""
    stop.set()
    try:
        while True:
            q.get_nowait()
    except queue.Empty:
        pass
    if thread.is_alive():
        thread.join(timeout=timeout)


def default_batch_spec(mesh: Mesh | None) -> PartitionSpec:
    """The CompiledTrainStep default input layout: batch dim 0 over every
    data-like axis present in the mesh, the SEQUENCE dim over 'sep'
    (context parallelism) when active."""
    if mesh is None:
        return PartitionSpec()
    data_axes = tuple(a for a in ("dp", "sharding")
                      if a in mesh.shape and mesh.shape[a] > 1)
    sep_on = "sep" in mesh.shape and mesh.shape["sep"] > 1
    return PartitionSpec(data_axes if data_axes else None,
                         "sep" if sep_on else None)


def trim_batch_spec(spec: PartitionSpec, shape, mesh: Mesh) -> PartitionSpec:
    """Per-dim: trim `spec` to this input's rank and drop any dim whose size
    doesn't divide its mesh axes (replicate it instead of crashing on a
    trailing partial batch)."""
    dims = list(tuple(spec))[: len(shape)]
    eff = []
    for d, entry in enumerate(dims):
        axes = [a for a in (entry if isinstance(entry, tuple) else (entry,))
                if a]
        div = 1
        for a in axes:
            div *= int(mesh.shape[a])
        eff.append(entry if (div > 1 and shape[d] % div == 0) or div == 1
                   else None)
    return PartitionSpec(*eff) if len(shape) else PartitionSpec()


def _tree_map(tree, fn):
    if isinstance(tree, (tuple, list)):
        return type(tree)(_tree_map(v, fn) for v in tree)
    if isinstance(tree, dict):
        return {k: _tree_map(v, fn) for k, v in tree.items()}
    return fn(tree)


class BatchSpecCache:
    """Trimmed per-input NamedShardings, computed once per batch SIGNATURE
    (the tuple of leaf shapes+dtypes) instead of once per step. Training
    loops see one or two signatures total (steady batches + one trailing
    partial), so the steady-state cost is a dict hit."""

    def __init__(self, mesh: Mesh | None, batch_spec: PartitionSpec | None):
        self.mesh = mesh
        self.batch_spec = (batch_spec if batch_spec is not None
                           else default_batch_spec(mesh))
        self._cache: dict = {}

    def signature(self, vals):
        return tuple((tuple(v.shape), str(v.dtype)) for v in vals)

    def shardings(self, vals) -> tuple:
        """One NamedSharding per (flat) input value; None mesh -> Nones."""
        if self.mesh is None:
            return (None,) * len(vals)
        key = self.signature(vals)
        hit = self._cache.get(key)
        if hit is None:
            hit = tuple(
                NamedSharding(self.mesh,
                              trim_batch_spec(self.batch_spec, v.shape,
                                              self.mesh))
                for v in vals)
            self._cache[key] = hit
        return hit

    def place(self, vals, shardings=None):
        """Place each value with its trimmed sharding, SKIPPING the transfer
        when the array is already committed to a matching sharding (the
        pre-placed fast path a DeviceFeeder batch takes). Values that do
        move go host->device DIRECTLY (numpy straight into the sharded
        buffer, no intermediate default-device copy) and in ONE batched
        device_put dispatch. Returns (placed_tuple, n_transferred)."""
        vals = tuple(v._value if isinstance(v, Tensor) else v for v in vals)
        vals = tuple(v if hasattr(v, "shape") and hasattr(v, "dtype")
                     else jnp.asarray(v) for v in vals)
        if shardings is None:
            shardings = self.shardings(vals)
        placed = list(vals)
        move = []
        for i, (v, sh) in enumerate(zip(vals, shardings)):
            if sh is None:
                if not isinstance(v, jax.Array):
                    placed[i] = jnp.asarray(v)
                continue
            if (isinstance(v, jax.Array)
                    and getattr(v, "committed", False)
                    and v.sharding == sh):
                continue  # already resident with the right layout
            move.append(i)
        if move:
            out = jax.device_put([vals[i] for i in move],
                                 [shardings[i] for i in move])
            for i, v in zip(move, out):
                placed[i] = v
        return tuple(placed), len(move)


class LossFuture:
    """Deferred device->host read of a step's loss. The jax array inside may
    still be computing; `float(f)` / `f.value()` blocks until the producing
    step finishes (and therefore every earlier step in program order)."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value._value if isinstance(value, Tensor) else value

    def ready(self) -> bool:
        try:
            return self._value.is_ready()
        except AttributeError:  # backends without is_ready: treat as ready
            return True

    def value(self) -> float:
        return float(self._value)

    def block(self):
        jax.block_until_ready(self._value)
        return self

    def __float__(self):
        return self.value()

    def __repr__(self):
        if self.ready():
            return f"LossFuture({float(self._value):.6g})"
        return "LossFuture(<pending>)"


class DispatchWindow:
    """Bound the number of un-fetched steps in flight. `admit(loss)` enqueues
    the new step's loss and, once more than `window` steps are pending,
    blocks on the OLDEST one — program order then guarantees at most
    `window` compiled steps (and their input batches) are queued on the
    device, so run-ahead cannot OOM HBM no matter how rarely the caller
    reads metrics."""

    def __init__(self, window: int | None = None):
        if window is None:
            from paddle_tpu.core.flags import flag

            window = int(flag("async_dispatch_window"))
        self.window = max(int(window), 1)
        self._pending: collections.deque = collections.deque()

    def admit(self, loss):
        loss = loss._value if isinstance(loss, Tensor) else loss
        self._pending.append(loss)
        while len(self._pending) > self.window:
            jax.block_until_ready(self._pending.popleft())

    def drain(self):
        while self._pending:
            jax.block_until_ready(self._pending.popleft())

    def __len__(self):
        return len(self._pending)


class FeederWorkerError(RuntimeError):
    """A DeviceFeeder worker crash, re-raised in the CONSUMER with the
    position attached: `batch_index` is the 0-based index (within this
    feeder's stream) of the batch being processed when the worker died, and
    `phase` says whether fetch/collate ('collate') or the sharded
    host->device placement ('device_put') failed — so a supervisor can
    rebuild the pipeline at the right cursor and an operator knows whether
    to suspect the dataset or the device. The original exception rides as
    ``__cause__``."""

    def __init__(self, phase: str, batch_index: int, cause: BaseException):
        super().__init__(
            f"DeviceFeeder worker crashed in {phase!r} of batch "
            f"{batch_index}: {cause!r}")
        self.phase = phase
        self.batch_index = batch_index


class _End:
    __slots__ = ()


class DeviceFeeder:
    """Run an iterator's fetch+collate+sharded-placement on a background
    thread, keeping up to `depth` fully-placed batches in flight.

    The consumer iterates placed batches (same tuple/list/dict structure,
    leaves are committed jax Arrays); `CompiledTrainStep` recognizes the
    matching shardings and skips its own `device_put`. Worker exceptions are
    re-raised in the consumer at the position they occurred; `close()` (also
    called on exhaustion and by the context manager) stops the worker,
    unblocks it, and JOINS the thread — no leaked prefetchers."""

    def __init__(self, iterator: Iterable, mesh: Mesh | None = None,
                 batch_spec: PartitionSpec | None = None,
                 depth: int | None = None):
        if depth is None:
            from paddle_tpu.core.flags import flag

            depth = int(flag("prefetch_to_device_depth")) or 2
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = depth
        self.spec_cache = BatchSpecCache(mesh, batch_spec)
        self.batches_placed = 0  # diagnostics
        self.leaves_transferred = 0
        # the data CURSOR an elastic checkpoint records: batches the
        # CONSUMER took (prefetched-but-unconsumed batches must be replayed
        # after a resume, so `batches_placed` would over-count)
        self.batches_consumed = 0
        self._it = iter(iterator)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"{THREAD_PREFIX}.DeviceFeeder")
        self._thread.start()

    # -- worker --------------------------------------------------------------
    def _place_batch(self, batch):
        flat = []
        _tree_map(batch, lambda v: (flat.append(v), v)[1])
        placed, moved = self.spec_cache.place(flat)
        self.leaves_transferred += moved
        self.batches_placed += 1
        it = iter(placed)
        return _tree_map(batch, lambda _v: next(it))

    def _put(self, item) -> bool:
        return interruptible_put(self._q, item, self._stop)

    def _run(self):
        phase = "collate"
        try:
            while not self._stop.is_set():
                phase = "collate"
                with obs_tracing.span("train.feed.fetch"):
                    try:
                        faults.point("feeder.collate")
                        batch = next(self._it)
                    except StopIteration:
                        break
                phase = "device_put"
                with obs_tracing.span("train.feed.place"):
                    faults.point("feeder.device_put")
                    placed = self._place_batch(batch)
                if not self._put(placed):
                    return
        except BaseException as e:  # propagate to the consumer, with the
            # cursor + phase attached (batches_placed = the index of the
            # batch that was being processed when the worker died)
            err = FeederWorkerError(phase, self.batches_placed, e)
            err.__cause__ = e
            self._err = err
        finally:
            self._put(_End)

    # -- consumer ------------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is _End:
            err = self._err
            # close() also DRAINS the bounded queue: prefetched device
            # batches queued behind the crash are freed (HBM back) and a
            # producer blocked on a full queue can never deadlock shutdown
            self.close()
            if err is not None:
                self._err = None
                raise err
            raise StopIteration
        self.batches_consumed += 1
        return item

    def close(self):
        """Stop the worker and join its thread (idempotent)."""
        stop_and_join(self._q, self._stop, self._thread)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def prefetch_to_device(iterator: Iterable, mesh: Mesh | None = None,
                       batch_spec: PartitionSpec | None = None,
                       depth: int = 2) -> DeviceFeeder:
    """tf.data-style prefetch-to-device: wrap `iterator` in a DeviceFeeder
    that keeps `depth` sharded, device-resident batches ready ahead of the
    training loop. Use as a context manager (or fully exhaust it) so the
    worker thread is joined."""
    return DeviceFeeder(iterator, mesh=mesh, batch_spec=batch_spec,
                        depth=depth)
