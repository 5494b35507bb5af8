"""Serving-side adapter pool: host registry + HBM slots, LRU + refcounts.

The `AdapterStore` is to adapter weights what the PR-9 `PageAllocator` is
to KV pages. Registered adapters live host-side (numpy — the cold tier,
never evicted while registered); a STATIC pool of ``G`` HBM slots per
target projection (``a_pool [G, d_in, r]`` / ``b_pool [G, r, d_out]``,
the ``alpha/r`` scale pre-baked into B) backs the engine's compiled
programs. `acquire()` pins an adapter into a slot (host->HBM swap-in on
miss, timed + journaled), `release()` unpins it, and a full pool evicts
the least-recently-used refcount-0 slot — a pinned adapter is never
evicted mid-request, exactly the page refcount contract.

Because the pools are fixed-shape jit arguments and each request's slot
id rides the decode/verify signature as one more per-row array, ANY mix
of tenants runs the same compiled program: swapping, evicting and
hot-swapping adapters changes pool VALUES only — zero retraces by
construction.

Failure shape: `AdapterLoadError` is a typed PER-REQUEST error (unknown
id, exhausted pool, or the ``serving.lora.swap_fail`` chaos point below).
The engine surfaces it at submit time, the replica propagates it, and
the router maps it to one terminal ``adapter_load_failed`` stream event
— a failed load costs one request one clean error, never a wedged
stream and never a breaker strike (the replica is healthy).
"""
from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np

from paddle_tpu.distributed.resilience import faults
from paddle_tpu.lora import seam
from paddle_tpu.lora.adapter import DEFAULT_TARGETS, find_targets
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import metrics as obs_metrics

__all__ = ["AdapterStore", "AdapterLoadError"]

faults.register(
    "serving.lora.swap_fail",
    "fail one adapter host->HBM swap-in at the AdapterStore: the request "
    "that needed it gets a typed AdapterLoadError (router surfaces ONE "
    "terminal adapter_load_failed event, no breaker strike, no failover) "
    "— other tenants' streams and the decode loop never notice")


class AdapterLoadError(RuntimeError):
    """Typed per-request adapter failure (unknown id / pool pinned full /
    swap-in failed): degrade the ONE request that asked, never the
    engine, the batch, or the stream transport."""

    def __init__(self, adapter_id: str, reason: str):
        super().__init__(f"adapter {adapter_id!r} failed to load: {reason}")
        self.adapter_id = str(adapter_id)
        self.reason = reason


import itertools as _itertools

_store_seq = _itertools.count()


def _register_store_metrics(store: "AdapterStore"):
    """Scrape-time collector (the engine-gauge idiom): residency, swap
    totals and latency mirror into the registry; the weakref owner
    unhooks a collected store automatically."""
    import weakref

    ref = weakref.ref(store)

    def collect(reg):
        s = ref()
        if s is None:
            return
        snap = s.residency()
        reg.gauge("lora_active_adapters",
                  "adapters resident in the HBM slot pool",
                  labels=("store",)).labels(store=s._metrics_id).set(
            float(len(snap["resident"])))
        reg.gauge("lora_registered_adapters",
                  "adapters registered in the host (cold) registry",
                  labels=("store",)).labels(store=s._metrics_id).set(
            float(snap["registered"]))
        reg.counter("lora_swap_total",
                    "adapter host->HBM swap-ins (pool loads + hot swaps)",
                    labels=("store",)).labels(
            store=s._metrics_id)._set_total(float(snap["swaps"]))
        reg.counter("lora_evictions_total",
                    "adapter slots evicted (LRU, refcount 0 only)",
                    labels=("store",)).labels(
            store=s._metrics_id)._set_total(float(snap["evictions"]))
        reg.gauge("lora_swap_ms",
                  "mean adapter swap-in latency (ms)",
                  labels=("store",)).labels(store=s._metrics_id).set(
            float(snap["swap_ms_mean"]))

    obs_metrics.registry().add_collector(collect, owner=store)


class AdapterStore:
    """Fixed-slot HBM adapter pool over a host-side registry for ONE
    base model's target projections (shapes discovered from the model —
    the same traversal `lora.attach` runs, so exported artifacts line up
    by construction)."""

    def __init__(self, model, *, rank: int, targets=DEFAULT_TARGETS,
                 slots: int = 0, dtype=None, block_rows: int = 8,
                 backend: str = "auto"):
        from paddle_tpu.core.flags import flag

        self.rank = int(rank)
        if self.rank <= 0:
            raise ValueError(f"adapter rank must be positive, got {rank}")
        self.num_slots = int(slots or flag("serving_adapter_slots"))
        if self.num_slots <= 0:
            raise ValueError(f"adapter pool needs >= 1 slot, got "
                             f"{self.num_slots}")
        self.targets = tuple(targets)
        self.block_rows = int(block_rows)
        self.backend = backend
        found = find_targets(model, self.targets)
        self._names = [n for n, _ in found]
        self._wids = [id(w) for _, w in found]
        self._dims = [(int(w.shape[0]), int(w.shape[1])) for _, w in found]
        if dtype is None:
            dt = np.dtype(found[0][1]._value.dtype)
        elif isinstance(dtype, str):
            from paddle_tpu.inference.artifact import np_dtype
            dt = np_dtype(dtype)
        else:
            dt = np.dtype(dtype)
        self.dtype = dt
        g, r = self.num_slots, self.rank
        self._a = [jnp.zeros((g, di, r), dt) for di, _ in self._dims]
        self._b = [jnp.zeros((g, r, do), dt) for _, do in self._dims]
        # host registry (cold tier): adapter id -> per-target (A, B*scale)
        self._host: dict[str, list] = {}
        self._slot_adapter: list[str | None] = [None] * g
        self._slot_by_id: dict[str, int] = {}
        self._refs = [0] * g
        self._tick = 0
        self._last_used = [0] * g
        self.swaps = 0
        self.swap_ms_total = 0.0
        self.evictions = 0
        self.load_failures = 0
        self._lock = threading.RLock()
        self._metrics_id = str(next(_store_seq))
        _register_store_metrics(self)

    # ---- registry (the cold tier) -----------------------------------------
    def register(self, adapter_id: str, source):
        """Register (or HOT-SWAP) an adapter: `source` is an artifact path
        or a `load_adapter()` blob. Validates rank + target coverage +
        factor shapes against the model-derived pool layout. If the id is
        already RESIDENT, its slot rows are rewritten in place — live
        requests pick the new weights up at their next dispatch (the
        pools ride as jit arguments, so no program ever recompiles)."""
        if isinstance(source, str):
            from paddle_tpu.lora.adapter import load_adapter
            source = load_adapter(source)
        meta, weights = source["adapter"], source["weights"]
        if int(meta["rank"]) != self.rank:
            raise ValueError(f"adapter {adapter_id!r}: rank {meta['rank']} "
                             f"!= store rank {self.rank}")
        missing = [n for n in self._names if n not in weights]
        if missing:
            raise ValueError(f"adapter {adapter_id!r} is missing factors "
                             f"for targets {missing}")
        scale = float(meta.get("alpha", self.rank)) / float(self.rank)
        rows = []
        for n, (di, do) in zip(self._names, self._dims):
            a, b = weights[n]
            a = np.asarray(a)
            b = np.asarray(b)
            if a.shape != (di, self.rank) or b.shape != (self.rank, do):
                raise ValueError(
                    f"adapter {adapter_id!r} target {n!r}: factor shapes "
                    f"{a.shape}/{b.shape} do not match the pool layout "
                    f"({(di, self.rank)}/{(self.rank, do)})")
            rows.append((a.astype(self.dtype),
                         (b.astype(np.float32) * scale).astype(self.dtype)))
        with self._lock:
            self._host[str(adapter_id)] = rows
            slot = self._slot_by_id.get(str(adapter_id))
            if slot is not None:          # hot swap under live traffic
                self._write_slot(slot, str(adapter_id), reason="hot_swap")

    def unregister(self, adapter_id: str):
        """Drop an adapter from the registry (and its slot when unpinned);
        a pinned adapter cannot be dropped mid-request."""
        aid = str(adapter_id)
        with self._lock:
            slot = self._slot_by_id.get(aid)
            if slot is not None:
                if self._refs[slot] > 0:
                    raise ValueError(f"adapter {aid!r} is pinned by "
                                     f"{self._refs[slot]} in-flight "
                                     f"request(s)")
                self._free_slot(slot)
            self._host.pop(aid, None)

    # ---- slot lifecycle (refcounted, LRU) ---------------------------------
    def acquire(self, adapter_id: str) -> int:
        """Pin `adapter_id` into a slot for one request (host->HBM swap-in
        on miss) and return the slot id — stable until the matching
        `release()`. Raises `AdapterLoadError` (typed, per-request) on an
        unknown id, a fully-pinned pool, or a chaos-failed swap."""
        aid = str(adapter_id)
        with self._lock:
            if aid not in self._host:
                self.load_failures += 1
                raise AdapterLoadError(aid, "not registered with the "
                                            "AdapterStore")
            slot = self._slot_by_id.get(aid)
            if slot is not None:
                self._refs[slot] += 1
                self._tick += 1
                self._last_used[slot] = self._tick
                return slot
            if faults.fire_check("serving.lora.swap_fail"):
                self.load_failures += 1
                raise AdapterLoadError(
                    aid, "host->HBM swap-in failed "
                         "(serving.lora.swap_fail)")
            slot = self._pick_slot()
            if slot is None:
                self.load_failures += 1
                raise AdapterLoadError(
                    aid, f"adapter pool exhausted: all {self.num_slots} "
                         f"slots pinned by in-flight requests")
            victim = self._slot_adapter[slot]
            if victim is not None:
                self._free_slot(slot)
                self.evictions += 1
                obs_events.emit("serving", "adapter_evict", severity="info",
                                adapter=victim, slot=slot, store=
                                self._metrics_id)
            self._write_slot(slot, aid, reason="load")
            self._refs[slot] = 1
            self._tick += 1
            self._last_used[slot] = self._tick
            return slot

    def release(self, adapter_id: str):
        aid = str(adapter_id)
        with self._lock:
            slot = self._slot_by_id.get(aid)
            if slot is not None and self._refs[slot] > 0:
                self._refs[slot] -= 1
                self._tick += 1
                self._last_used[slot] = self._tick

    def slot_of(self, adapter_id: str) -> int:
        """Resident slot of a PINNED adapter (the engine packs this into
        the per-row slot array each dispatch)."""
        with self._lock:
            slot = self._slot_by_id.get(str(adapter_id))
            if slot is None:
                raise KeyError(f"adapter {adapter_id!r} is not resident")
            return slot

    def _pick_slot(self):
        free = [i for i, a in enumerate(self._slot_adapter) if a is None]
        if free:
            return free[0]
        idle = [i for i in range(self.num_slots) if self._refs[i] == 0]
        if not idle:
            return None
        return min(idle, key=lambda i: self._last_used[i])

    def _free_slot(self, slot: int):
        aid = self._slot_adapter[slot]
        if aid is not None:
            self._slot_by_id.pop(aid, None)
        self._slot_adapter[slot] = None
        self._refs[slot] = 0

    def _write_slot(self, slot: int, adapter_id: str, reason: str):
        """The swap-in: write one adapter's factors into row `slot` of
        every target's pools (eager `.at[].set` — compiled scatter
        programs, the `_copy_page` idiom; the decode program itself never
        changes). Timed + journaled: this is the latency a cold tenant
        pays once, and the hot-swap latency `swap_ms_mean` reports."""
        t0 = time.perf_counter()
        rows = self._host[adapter_id]
        for i, (a, b) in enumerate(rows):
            self._a[i] = self._a[i].at[slot].set(jnp.asarray(a))
            self._b[i] = self._b[i].at[slot].set(jnp.asarray(b))
        self._slot_adapter[slot] = adapter_id
        self._slot_by_id[adapter_id] = slot
        ms = (time.perf_counter() - t0) * 1e3
        self.swaps += 1
        self.swap_ms_total += ms
        obs_events.emit("serving", "adapter_swap", severity="info",
                        adapter=adapter_id, slot=slot, reason=reason,
                        ms=round(ms, 3), store=self._metrics_id)

    # ---- what the compiled programs consume --------------------------------
    def pools(self):
        """The (a_pools, b_pools) jit arguments for one dispatch — plain
        lists of fixed-shape arrays, snapshotted under the lock so a
        concurrent hot-swap can't tear one dispatch's view."""
        with self._lock:
            return list(self._a), list(self._b)

    def bind(self, a_pools, b_pools, slots):
        """Context manager used INSIDE traced programs: exposes the traced
        pool/slot arguments to `F.linear` via the seam."""
        pools = {wid: (a, b)
                 for wid, a, b in zip(self._wids, a_pools, b_pools)}
        return seam.serve_bind(seam.ServeBinding(
            pools, slots, self.num_slots,
            block_rows=self.block_rows, backend=self.backend))

    def validate_model(self, model):
        """The engine's construction check: the store must have been built
        against THIS model object (weight identity keys the seam)."""
        ids = {id(p) for p in model.parameters()}
        if not all(w in ids for w in self._wids):
            raise ValueError(
                "AdapterStore was built for a different model instance; "
                "construct it from the model the engine serves")

    # ---- observability -----------------------------------------------------
    @property
    def swap_ms_mean(self) -> float:
        return self.swap_ms_total / self.swaps if self.swaps else 0.0

    def residency(self) -> dict:
        """The /stats adapter snapshot: who is resident where, pinned by
        how many requests, plus swap/eviction totals."""
        with self._lock:
            return {
                "slots": self.num_slots,
                "rank": self.rank,
                "registered": len(self._host),
                "resident": [a for a in self._slot_adapter if a is not None],
                "refs": {a: self._refs[s]
                         for a, s in self._slot_by_id.items()},
                "swaps": self.swaps,
                "swap_ms_mean": round(self.swap_ms_mean, 3),
                "evictions": self.evictions,
                "load_failures": self.load_failures,
            }
