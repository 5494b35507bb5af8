"""Elastic training checkpoints: async snapshot-to-host, crash-consistent
commit, cross-mesh resume (ROADMAP item 5).

Reference analog: the fleet elastic layer + ``distributed/checkpoint``
resharded save/load the reference pairs with TCPStore rendezvous (PAPER.md
layer 2). TPU-native restatement, three pieces:

**Async snapshot (no step blocked).** `capture()` turns a
`CompiledTrainStep`'s full training state — params (split per layer from the
scan stack), optimizer moments, fp8 amax histories, GradScaler scalars, step
counter, RNG key, data cursor — into donation-safe on-device copies. Copies
are DISPATCHED, never read: the caller returns to `step_async()` immediately
and run-ahead continues. A writer thread (the `io/device_feed.py` DeviceFeeder
template: bounded queue, joined on close, `paddle_tpu.ckpt` thread-name
prefix for the hygiene guard) performs the device->host readback of only the
ADDRESSABLE shards and the file I/O off the critical path.

**Crash-consistent commit.** Shard containers land under ``tmp/step_N/`` and
are fsync'd; the coordinator merges their shard tables into the global
metadata, renames the directory into place, and only then writes the
``COMMIT`` marker (after a TCPStore barrier when multi-host). `latest()`
resolves ONLY committed snapshots, so a kill at ANY point — mid shard write,
before the rename, between rename and marker — leaves the previous committed
checkpoint loadable. Keep-last-K GC runs after commit and never touches the
newest committed snapshot. Every phase boundary honors the
unified fault registry's ``ckpt.*`` points (`FAULT_POINTS`; the legacy
``FLAGS_ckpt_fault_injection`` knob still arms them), which the
crash-consistency tests drive.

**Cross-mesh resume.** Snapshots store mesh-agnostic NAMES (model state-dict
keys; optimizer slots keyed by the owning parameter's name) and
`load_state_dict.read_global_state` reconstructs full arrays from any shard
layout, so a dp=8 save resumes on dp=4, a scan save resumes unrolled, a
zero3-sharded save resumes replicated (and each vice versa), and — through
`rename_arrays` + the pipeline runtimes' resuming `init_opt_states` — a
single-program save resumes under pipeline parallelism. The target step
re-shards everything for its own mesh at construction.

Preemption: `install_preemption_handler` (SIGTERM -> save-and-exit with a
watchdog diagnostic dump) and `install_hang_handler` (a
`watchdog.CommTaskManager` hang fires the same path, dump first).
"""
from __future__ import annotations

import glob
import json
import os
import queue
import shutil
import signal
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.resilience import faults

__all__ = [
    "FAULT_POINTS", "CheckpointFaultInjected", "Snapshot", "capture",
    "capture_model", "capture_modules", "restore", "rename_arrays",
    "CheckpointManager", "install_preemption_handler",
    "install_hang_handler",
]

FAULT_POINTS = ("after_snapshot", "after_shard_write", "after_metadata",
                "before_rename", "before_commit", "after_commit")

_STATE_JSON = "state.json"
_COMMIT = "COMMIT"
_TMP = "tmp"


class CheckpointFaultInjected(faults.FaultInjected):
    """Raised at an armed ckpt.* fault point — the tests' stand-in for
    a kill -9 at that exact phase of the commit protocol. Armed through the
    unified registry (resilience.faults) or the legacy
    FLAGS_ckpt_fault_injection string knob."""


_PHASE_DOCS = {
    "after_snapshot": "after the donation-safe device copies, before any "
                      "readback/IO",
    "after_shard_write": "shard container written+fsync'd, before the "
                         "written barrier",
    "after_metadata": "global metadata merged and written, before the "
                      "publish rename",
    "before_rename": "the last instant the snapshot is still invisible",
    "before_commit": "renamed into place but no COMMIT marker yet",
    "after_commit": "committed; GC has not run",
}
for _p in FAULT_POINTS:
    faults.register(f"ckpt.{_p}",
                    f"elastic-checkpoint commit protocol: {_PHASE_DOCS[_p]}",
                    exc=CheckpointFaultInjected,
                    legacy_flag=("ckpt_fault_injection", _p))


def _maybe_inject(point: str):
    faults.point(f"ckpt.{point}")


def _step_dirname(step: int) -> str:
    return f"step_{int(step):08d}"


def _parse_step(name: str):
    if not name.startswith("step_"):
        return None
    try:
        return int(name[len("step_"):])
    except ValueError:
        return None


def _device_copy(v):
    """A donation-safe snapshot of one leaf: jax Arrays get an on-device copy
    (dispatched, not read — the ORIGINAL buffer may be donated to the next
    step while the copy computes), host values pass through as numpy."""
    if isinstance(v, jax.Array):
        return jnp.copy(v)
    return np.asarray(v)


# one jitted optimization_barrier over ALL leaves: produces bit-exact new
# buffers (no input forwarding/aliasing without donation) in a single
# dispatch, instead of one eager jnp.copy dispatch per leaf — the per-save
# caller-thread cost of a capture. jit caches per
# (structure, shapes), which is stable across a training run's saves.
_copy_jit = None


def _device_copy_tree(named: dict) -> dict:
    global _copy_jit
    jax_keys = [k for k, v in named.items() if isinstance(v, jax.Array)]
    jax_set = set(jax_keys)
    out = {k: np.asarray(v) for k, v in named.items() if k not in jax_set}
    if jax_keys:
        try:
            if _copy_jit is None:
                _copy_jit = jax.jit(
                    lambda xs: jax.lax.optimization_barrier(xs))
            copies = _copy_jit([named[k] for k in jax_keys])
        except Exception:  # older jax / exotic arrays: per-leaf fallback
            copies = [_device_copy(named[k]) for k in jax_keys]
        out.update(zip(jax_keys, copies))
    return out


@dataclass
class Snapshot:
    """One capture: `arrays` name -> device array (or numpy), `meta` a
    JSON-able dict (step/fp8 layout/scaler/cursor/diagnostics)."""

    step: int
    arrays: dict
    meta: dict = field(default_factory=dict)


def capture(step, cursor=None) -> Snapshot:
    """Snapshot a CompiledTrainStep WITHOUT blocking its dispatch stream:
    `named_train_state()` hands out live device arrays under mesh-agnostic
    names; each is copied on-device (donation-safe) and the readback happens
    on the CheckpointManager writer thread. `cursor` is the caller's data
    position (e.g. DeviceFeeder.batches_consumed) and rides in meta."""
    arrays, meta = step.named_train_state()
    if cursor is not None:
        meta["cursor"] = cursor
    return Snapshot(step=int(step.step_count),
                    arrays=_device_copy_tree(arrays), meta=meta)


def capture_model(network, optimizer=None, step=None, cursor=None) -> Snapshot:
    """Eager-layer capture (the hapi path without a compiled step): model
    state dict + optimizer moments keyed by parameter name."""
    from paddle_tpu.parallel.train_step import _innermost_opt

    arrays = {}
    for name, t in network.state_dict().items():
        arrays[f"model/{name}"] = t._value
    count = 0
    if optimizer is not None:
        opt = _innermost_opt(optimizer)
        count = int(getattr(opt, "_step_count", 0) or 0)
        id2name = {id(t): n for n, t in network.state_dict().items()}
        for p in opt._params:
            name = id2name.get(id(p))
            st = opt._state.get(id(p))
            if name is None or not st:
                continue
            for k, v in st.items():
                arrays[f"opt/{name}/{k}"] = v
    meta: dict = {"step": count}
    if cursor is not None:
        meta["cursor"] = cursor
    return Snapshot(step=int(step if step is not None else count),
                    arrays=_device_copy_tree(arrays), meta=meta)


def capture_modules(named_modules: dict, optimizer=None, step: int = 0,
                    cursor=None) -> Snapshot:
    """Capture a MULTI-module topology (pipeline stages) under canonical
    names: `named_modules` maps a canonical prefix to a module, e.g.
    ``{"llama.": embed_stage, "llama.layers.0.": block0, ...,
    "llama.norm.": head.norm, "lm_head.": head.lm_head}`` — each module's
    state-dict names are prefixed into the single-model namespace, so the
    snapshot resumes interchangeably with a `capture()` one (pp on <-> off).
    Sync the runtime's device state back first
    (`sync_params_to_model`/`sync_states_to_optimizer`)."""
    from paddle_tpu.parallel.train_step import _innermost_opt

    arrays: dict = {}
    id2name: dict = {}
    for prefix, module in named_modules.items():
        for name, t in module.state_dict().items():
            arrays[f"model/{prefix}{name}"] = t._value
            id2name.setdefault(id(t), f"{prefix}{name}")
    if optimizer is not None:
        opt = _innermost_opt(optimizer)
        step = step or int(getattr(opt, "_step_count", 0) or 0)
        for p in opt._params:
            name = id2name.get(id(p))
            st = opt._state.get(id(p))
            if name is None or not st:
                continue
            for k, v in st.items():
                arrays[f"opt/{name}/{k}"] = v
    meta: dict = {"step": int(step)}
    if cursor is not None:
        meta["cursor"] = cursor
    return Snapshot(step=int(step), arrays=_device_copy_tree(arrays),
                    meta=meta)


def rename_arrays(arrays: dict, mapper) -> dict:
    """Re-key a loaded snapshot's arrays. `mapper` is a callable
    ``name -> new_name | None`` (None drops the entry) or a dict of
    ``old_prefix -> new_prefix`` (longest matching prefix wins) — the
    cross-topology glue, e.g. mapping ``model/llama.layers.3.`` onto a
    pipeline block's local names."""
    if isinstance(mapper, dict):
        prefixes = sorted(mapper, key=len, reverse=True)

        def fn(name):
            for p in prefixes:
                if name.startswith(p):
                    return mapper[p] + name[len(p):]
            return None
    else:
        fn = mapper
    out = {}
    for name, v in arrays.items():
        new = fn(name)
        if new is not None:
            out[new] = v
    return out


def restore(arrays: dict, meta: dict, model, optimizer=None, mapper=None):
    """Load a snapshot (from CheckpointManager.load) into `model` (+
    optimizer moments and step count), BEFORE constructing the train step —
    the step constructor then re-shards params/moments for the target mesh
    (dp width, zero stage, scan packing all re-derived). Entries whose names
    the model doesn't own are ignored, so a multi-module topology (pipeline
    stages) restores by calling this once per module with a `mapper`
    (see rename_arrays). Returns (missing, unexpected) from set_state_dict."""
    if mapper is not None:
        arrays = rename_arrays(arrays, mapper)
    own = model.state_dict()
    model_sd = {name[len("model/"):]: v for name, v in arrays.items()
                if name.startswith("model/")}
    result = model.set_state_dict(
        {k: v for k, v in model_sd.items() if k in own})
    if optimizer is not None:
        from paddle_tpu.parallel.train_step import _innermost_opt

        opt = _innermost_opt(optimizer)
        slots: dict = {}
        for name, v in arrays.items():
            if not name.startswith("opt/"):
                continue
            pname, slot = name[len("opt/"):].rsplit("/", 1)
            slots.setdefault(pname, {})[slot] = v
        for pname, st in slots.items():
            t = own.get(pname)
            if t is None:
                continue
            opt._state[id(t)] = {k: jnp.asarray(np.asarray(v))
                                 for k, v in st.items()}
        opt._step_count = int(meta.get("step", 0))
    return result


class _SaveHandle:
    """Completion handle for one async save: `wait()` blocks until the
    writer finished this snapshot (re-raising its error, fault injections
    included)."""

    __slots__ = ("step", "_done", "_err")

    def __init__(self, step):
        self.step = step
        self._done = threading.Event()
        self._err = None

    def done(self) -> bool:
        return self._done.is_set()

    def error(self):
        """The writer's exception for this snapshot (None while in flight
        or on success) — the non-blocking probe a supervisor reaps failed
        saves with."""
        return self._err if self._done.is_set() else None

    def wait(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError(f"checkpoint save of step {self.step} "
                               f"still in flight")
        if self._err is not None:
            raise self._err
        return self


class CheckpointManager:
    """Commit-protocol checkpoint directory + async writer thread.

    ``root/step_NNNNNNNN/`` holds committed snapshots (shard containers +
    JSON metadata + ``state.json`` + ``COMMIT``); ``root/tmp/`` holds
    in-progress writes. `latest()`/`load()` see only committed steps; `save`
    / `save_async` run the crash-consistent protocol (class docstring of the
    module). `store`/`world_size`/`rank` wire the multi-host barrier; the
    defaults are the single-host (one-process-per-pod-host SPMD) case.
    """

    def __init__(self, root: str, keep_last: int | None = None,
                 store=None, world_size: int | None = None,
                 rank: int | None = None, coordinator_rank: int = 0,
                 job_id: str = "ckpt"):
        from paddle_tpu.core.flags import flag
        from paddle_tpu.distributed.env import get_rank, get_world_size

        self.root = str(root)
        self.keep_last = int(flag("ckpt_keep_last")
                             if keep_last is None else keep_last)
        self.store = store
        self.world = int(get_world_size() if world_size is None
                         else world_size)
        self.rank = int(get_rank() if rank is None else rank)
        self.coordinator_rank = int(coordinator_rank)
        self.job_id = job_id
        os.makedirs(self.root, exist_ok=True)
        self.preempt_reason: str | None = None
        self._q: queue.Queue = queue.Queue(maxsize=2)
        self._thread: threading.Thread | None = None
        self._closing = False
        self._handles: list[_SaveHandle] = []
        self._lock = threading.Lock()
        # serializes _write_snapshot between the writer thread and SYNC
        # saves (SIGTERM/hang handlers): without it a same-step pair races
        # on tmp/step_N, and a sync commit's GC could rmtree the async
        # save's still-in-progress tmp dir. A plain Lock would self-deadlock
        # if a signal lands while the MAIN thread is itself inside save();
        # `writing_in_this_thread` lets the handler detect that case and
        # skip its save entirely (re-entering the protocol would rename the
        # interrupted save's tmp dir out from under it).
        self._write_lock = threading.Lock()
        self._write_tls = threading.local()
        self._last_barrier_step: int | None = None

    # -- resolution ----------------------------------------------------------
    def _is_committed(self, step: int) -> bool:
        return os.path.exists(os.path.join(self.root, _step_dirname(step),
                                           _COMMIT))

    def steps(self) -> list:
        """All COMMITTED snapshot steps, ascending."""
        out = []
        for name in os.listdir(self.root):
            step = _parse_step(name)
            if step is not None and self._is_committed(step):
                out.append(step)
        return sorted(out)

    def latest(self):
        """Newest committed step, or None. Uncommitted directories (a crash
        between rename and COMMIT) are invisible here."""
        steps = self.steps()
        return steps[-1] if steps else None

    def path(self, step: int) -> str:
        return os.path.join(self.root, _step_dirname(step))

    def load(self, step: int | None = None):
        """(arrays, meta) of a committed snapshot (default: latest). Arrays
        come back as full global numpy arrays regardless of the mesh they
        were saved under (read_global_state reconstruction)."""
        from paddle_tpu.distributed.checkpoint.load_state_dict import (
            read_global_state)

        if step is None:
            step = self.latest()
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {self.root!r}")
        if not self._is_committed(step):
            raise FileNotFoundError(
                f"step {step} has no COMMIT marker under {self.root!r} "
                f"(crashed save?); latest committed is {self.latest()}")
        path = self.path(step)
        with open(os.path.join(path, _STATE_JSON)) as f:
            meta = json.load(f)
        return read_global_state(path), meta

    # -- preemption ----------------------------------------------------------
    def request_preempt(self, reason: str):
        """Mark the job preempted (SIGTERM / watchdog hang); training loops
        poll `should_stop` and exit after the save."""
        self.preempt_reason = reason

    def clear_preempt(self):
        """Un-mark preemption — the resilience supervisor calls this after
        an in-process restart from a hang (the checkpoint the hang handler
        committed has been restored; training may continue)."""
        self.preempt_reason = None

    @property
    def should_stop(self) -> bool:
        return self.preempt_reason is not None

    # -- write path ----------------------------------------------------------
    def save(self, snapshot: Snapshot) -> _SaveHandle:
        """Synchronous save: runs the full commit protocol on the calling
        thread (SIGTERM/save-and-exit path). Raises on failure — including
        injected faults — leaving the previous committed snapshot intact."""
        h = _SaveHandle(snapshot.step)
        try:
            self._write_snapshot(snapshot)
        except BaseException as e:
            h._err = e
            raise
        finally:
            h._done.set()
        return h

    def save_async(self, snapshot: Snapshot) -> _SaveHandle:
        """Enqueue a snapshot for the writer thread; returns immediately
        (bounded queue: blocks only when 2 saves are already in flight —
        backpressure instead of unbounded snapshot memory). Errors surface
        on the handle and on `wait()`."""
        self._ensure_thread()
        h = _SaveHandle(snapshot.step)
        with self._lock:
            self._handles.append(h)
        self._q.put((snapshot, h))
        return h

    def wait(self):
        """Block until every queued save finished; re-raise the first
        failure (fault injections included)."""
        with self._lock:
            handles, self._handles = self._handles, []
        err = None
        for h in handles:
            h._done.wait()
            if err is None and h._err is not None:
                err = h._err
        if err is not None:
            raise err

    def close(self, timeout: float = 60.0):
        """Finish queued saves, stop and JOIN the writer thread (the
        thread-hygiene contract). Idempotent; errors already surfaced via
        handles are not re-raised here. If the writer is still mid-write
        after `timeout` it is NOT detached — a warning fires and a later
        close()/save_async reuses the live thread instead of orphaning it."""
        if self._thread is not None:
            if not self._closing:
                self._closing = True
                self._q.put(None)
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                import warnings

                warnings.warn(
                    f"checkpoint writer still busy after {timeout:.0f}s "
                    f"(large snapshot / slow storage?); not detaching — "
                    f"call close() again to finish joining")
            else:
                self._thread = None
                self._closing = False

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False

    def _ensure_thread(self):
        if self._closing:
            # a timed-out close() left the writer draining toward its stop
            # sentinel; a new job behind that sentinel would never run
            raise RuntimeError(
                "CheckpointManager is closing (writer still draining); "
                "call close() to completion before saving again")
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._writer_loop, daemon=True,
                name="paddle_tpu.ckpt.writer")
            self._thread.start()

    def _writer_loop(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            snapshot, handle = item
            try:
                self._write_snapshot(snapshot)
            except BaseException as e:
                handle._err = e
            finally:
                handle._done.set()

    # -- the commit protocol -------------------------------------------------
    def _barrier(self, tag: str, step: int):
        if self.store is not None and self.world > 1:
            self.store.barrier(f"{self.job_id}/{step}/{tag}", self.world,
                               rank=self.rank)

    def _cleanup_barriers(self, step: int):
        """Delete the PREVIOUS save's barrier keys (coordinator): steps are
        monotonic, so by the time save N runs every rank has left save
        N-1's barriers — deleting the current save's keys right after
        release could strand a straggler still inside wait()."""
        if self.store is None or self.world <= 1:
            return
        for tag in ("written", "committed"):
            name = f"{self.job_id}/{step}/{tag}"
            self.store.delete_key(f"__barrier__/{name}")
            self.store.delete_key(f"__barrier_done__/{name}")
            for r in range(self.world):
                self.store.delete_key(f"__barrier_arrived__/{name}/{r}")

    @property
    def writing_in_this_thread(self) -> bool:
        """True while the CURRENT thread is inside the commit protocol —
        the preemption handler must not re-enter it (the interrupted save
        completes when the handler returns)."""
        return bool(getattr(self._write_tls, "writing", False))

    def _write_snapshot(self, snapshot: Snapshot):
        """tmp write -> fsync -> metadata -> rename -> COMMIT -> GC, with a
        ``ckpt.*`` fault-point check at every phase boundary."""
        if self.writing_in_this_thread:
            raise RuntimeError(
                "re-entrant checkpoint save on the same thread (signal "
                "handler during a sync save?) — the in-progress save "
                "already covers this state")
        from paddle_tpu.observability import events as _events
        from paddle_tpu.observability import tracing as _tracing

        with self._write_lock:
            self._write_tls.writing = True
            try:
                # the "checkpoint commit" phase span of the training-step
                # timeline (docs/observability.md) — the writer thread's
                # work lands on the same exported trace as the train loop
                with _tracing.span("ckpt.commit", component="ckpt",
                                   step=int(snapshot.step)):
                    out = self._write_snapshot_locked(snapshot)
                _events.emit("ckpt", "commit", step=int(snapshot.step),
                             root=self.root)
                return out
            finally:
                self._write_tls.writing = False

    def _write_snapshot_locked(self, snapshot: Snapshot):
        from paddle_tpu.distributed.checkpoint import format as ckpt_format
        from paddle_tpu.distributed.checkpoint.metadata import Metadata
        from paddle_tpu.distributed.checkpoint.save_state_dict import (
            collect_shards, merge_metas)

        step = int(snapshot.step)
        is_coord = self.rank == self.coordinator_rank
        if (is_coord and self._last_barrier_step is not None
                and self._last_barrier_step != step):
            self._cleanup_barriers(self._last_barrier_step)
        self._last_barrier_step = step
        final_dir = self.path(step)
        tmp_dir = os.path.join(self.root, _TMP, _step_dirname(step))
        if is_coord and os.path.isdir(final_dir):
            if self._is_committed(step):
                raise FileExistsError(
                    f"step {step} is already committed under {self.root!r}")
            shutil.rmtree(final_dir)  # uncommitted leftover of a crash
        os.makedirs(tmp_dir, exist_ok=True)

        # phase 0: the device->host readback. `arrays` may hold still-
        # computing on-device copies; np.asarray here (THIS thread) is the
        # only point that blocks on them. Only addressable shards are pulled.
        fname = f"{self.rank}_0.distcp"
        _maybe_inject("after_snapshot")
        meta, data = collect_shards(dict(snapshot.arrays), fname)

        # phase 1: shard container, fsync'd before anything references it
        ckpt_format.write_shard_file(os.path.join(tmp_dir, fname), data)
        ckpt_format.fsync_dir(tmp_dir)
        _maybe_inject("after_shard_write")
        self._barrier("written", step)

        # phase 2 (coordinator): the global metadata view is merged from the
        # shard tables ON DISK (not exchanged over the network), so a
        # metadata file can never describe bytes that didn't land
        if is_coord:
            from paddle_tpu.distributed.checkpoint.metadata import (
                LocalTensorIndex, LocalTensorMetadata)

            metas = [meta]
            for f in sorted(glob.glob(os.path.join(tmp_dir, "*.distcp"))):
                if os.path.basename(f) != fname:
                    m = Metadata()
                    for ent in ckpt_format.shard_table(f):
                        off = tuple(int(o) for o in ent["offset"])
                        m.state_dict_metadata.setdefault(ent["key"], []).append(
                            LocalTensorMetadata(off, tuple(ent["shape"]),
                                                ent["dtype"]))
                        m.storage_metadata[
                            LocalTensorIndex(ent["key"], off)] = (
                                os.path.basename(f))
                    metas.append(m)
            ckpt_format.write_metadata(
                os.path.join(tmp_dir, "0.metadata"), merge_metas(metas))
            doc = dict(snapshot.meta)
            doc["step"] = step
            with open(os.path.join(tmp_dir, _STATE_JSON), "w") as f:
                json.dump(doc, f)
                f.flush()
                os.fsync(f.fileno())
            ckpt_format.fsync_dir(tmp_dir)
        _maybe_inject("after_metadata")

        # phase 3 (coordinator): publish by rename — atomic on POSIX, so
        # `step_N` either fully exists or not at all
        _maybe_inject("before_rename")
        if is_coord:
            os.replace(tmp_dir, final_dir)
            ckpt_format.fsync_dir(self.root)
            # phase 4: the COMMIT marker makes it loadable; a kill between
            # rename and here leaves step_N invisible to latest()
            _maybe_inject("before_commit")
            with open(os.path.join(final_dir, _COMMIT), "w") as f:
                json.dump({"step": step, "format": ckpt_format.FORMAT_NAME},
                          f)
                f.flush()
                os.fsync(f.fileno())
            ckpt_format.fsync_dir(final_dir)
        self._barrier("committed", step)
        _maybe_inject("after_commit")
        if is_coord:
            self._gc(step)

    def _gc(self, just_committed: int):
        """Keep the last K committed snapshots; also clear stale tmp and
        uncommitted step dirs OLDER than the newest committed one (failed
        attempts that can never become loadable)."""
        committed = self.steps()
        if self.keep_last > 0:
            for step in committed[:-self.keep_last]:
                shutil.rmtree(self.path(step), ignore_errors=True)
        newest = committed[-1] if committed else just_committed
        for name in os.listdir(self.root):
            step = _parse_step(name)
            if (step is not None and step < newest
                    and not self._is_committed(step)):
                shutil.rmtree(os.path.join(self.root, name),
                              ignore_errors=True)
        tmp_root = os.path.join(self.root, _TMP)
        if os.path.isdir(tmp_root):
            for name in os.listdir(tmp_root):
                step = _parse_step(name)
                if step is not None and step <= newest:
                    shutil.rmtree(os.path.join(tmp_root, name),
                                  ignore_errors=True)


def install_preemption_handler(manager: CheckpointManager,
                               capture_fn: Callable[[], Snapshot],
                               signals=(signal.SIGTERM,)) -> Callable[[], None]:
    """SIGTERM -> save-and-exit: synchronously run the commit protocol on
    `capture_fn()`'s snapshot, write the watchdog diagnostic dump, and mark
    the manager preempted so training loops (`manager.should_stop`, the hapi
    AutoCheckpoint callback) wind down. Returns an uninstall callable.
    Must be called from the main thread (CPython signal contract)."""
    prev = {}

    def handler(signum, frame):
        manager.request_preempt(f"signal {signum}")
        from paddle_tpu.distributed import watchdog

        state = watchdog.dump_state()
        if manager.writing_in_this_thread:
            # the signal interrupted a sync save already in progress on
            # this thread — it resumes and commits when we return;
            # re-entering the protocol would corrupt its tmp dir
            return
        snap = capture_fn()
        snap.meta = dict(snap.meta)
        snap.meta["preempt"] = {"signal": int(signum),
                                "in_flight": state["in_flight"]}
        try:
            manager.save(snap)
        except FileExistsError:
            pass  # this exact step was already committed (e.g. a cadence
            # save that just landed) — the state IS durable, don't abort

    for s in signals:
        prev[s] = signal.signal(s, handler)

    def uninstall():
        for s, h in prev.items():
            signal.signal(s, h)

    return uninstall


def install_hang_handler(manager: CheckpointManager,
                         capture_fn: Callable[[], Snapshot],
                         watchdog_manager=None) -> Callable[[], None]:
    """Wire a watchdog hang to save-and-exit: when a dispatched step's
    readback times out, the listener writes the structured diagnostic dump
    FIRST (the dump must survive even if the device is wedged enough that
    the save itself blocks), then best-effort saves `capture_fn()` with the
    diagnostics attached, then requests preemption. Returns the listener's
    uninstall callable."""
    from paddle_tpu.distributed import watchdog

    def on_hang(task, diagnostics):
        try:
            snap = capture_fn()
            snap.meta = dict(snap.meta)
            snap.meta["hang"] = diagnostics
            try:
                manager.save(snap)
            except FileExistsError:
                pass  # this step is already durably committed
        finally:
            manager.request_preempt(f"hang: {task.name}")

    return watchdog.add_hang_listener(on_hang, manager=watchdog_manager)
