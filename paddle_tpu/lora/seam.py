"""The LoRA dispatch seam: what `F.linear` consults per projection call.

Two registration planes share one lookup point:

* TRAIN plane — `adapter.attach()` registers per-weight A/B Parameters
  keyed by ``id(weight)`` (the Parameter object every forward resolves
  through ``Layer.__getattr__`` is stable, eagerly and under
  ``functional_call``'s in-place value binding). `F.linear` adds
  ``scale * (x @ A) @ B`` with A/B riding as apply_op inputs, so the
  delta differentiates like any other parameter.
* SERVE plane — a thread-local `ServeBinding` the `AdapterStore` installs
  INSIDE the engine's traced decode/verify/prefill programs: per-weight
  adapter POOLS (``[G, d_in, r]`` / ``[G, r, d_out]``) plus the per-row
  slot ids. The delta gathers each row's adapter through the grouped
  (ragged) Pallas matmul — heterogeneous adapters in one dispatch, pool
  shape static, so mixing tenants never retraces.

This module is deliberately light (stdlib + lazy jax): `nn.functional`
imports it at module load and must not drag the serving stack in.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

__all__ = ["TrainEntry", "ServeBinding", "train_register", "train_clear",
           "train_lookup", "serve_bind", "serve_binding", "active",
           "serve_delta"]


class TrainEntry:
    """One adapted weight's train-mode factors (A [in, r], B [r, out]
    Parameters) and the baked ``alpha / rank`` scale."""

    __slots__ = ("A", "B", "scale")

    def __init__(self, A, B, scale: float):
        self.A = A
        self.B = B
        self.scale = float(scale)


class ServeBinding:
    """The serve-mode view F.linear reads inside a traced program:
    ``pools[id(weight)] -> (a_pool, b_pool)`` tracers (scale pre-baked
    into b_pool rows), per-row ``slots`` (int32, one per batch row;
    ``num_slots`` marks rows without an adapter — the grouped matmul's
    trash id, zero delta), and the grouped-matmul launch knobs."""

    __slots__ = ("pools", "slots", "num_slots", "block_rows", "backend")

    def __init__(self, pools: dict, slots, num_slots: int,
                 block_rows: int = 8, backend: str = "auto"):
        self.pools = pools
        self.slots = slots
        self.num_slots = int(num_slots)
        self.block_rows = int(block_rows)
        self.backend = backend


_train_entries: dict[int, TrainEntry] = {}
_tls = threading.local()


def train_register(wid: int, entry: TrainEntry):
    _train_entries[wid] = entry


def train_clear(wids):
    for wid in wids:
        _train_entries.pop(wid, None)


def train_lookup(wid: int) -> TrainEntry | None:
    return _train_entries.get(wid)


def serve_binding() -> ServeBinding | None:
    return getattr(_tls, "binding", None)


@contextmanager
def serve_bind(binding: ServeBinding):
    prev = getattr(_tls, "binding", None)
    _tls.binding = binding
    try:
        yield binding
    finally:
        _tls.binding = prev


def active() -> bool:
    """The one-branch fast check F.linear pays when no adapter is
    attached or bound anywhere (the overwhelmingly common case)."""
    return bool(_train_entries) or getattr(_tls, "binding", None) is not None


def serve_delta(v, a_pool, b_pool, binding: ServeBinding):
    """Per-row heterogeneous adapter delta for one projection: flatten
    ``v [..., d]`` to rows, repeat the per-batch-row slot ids across the
    token dim (row-major reshape keeps row ``b*T + t`` owned by batch row
    ``b``), pad rows to the block grid with trash ids, and gather each
    row's adapter through two grouped matmuls. Exact per row for ANY slot
    mix (the pallas backend masks within blocks), so a heterogeneous
    batch is bit-equal to serving each adapter alone."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    backend, bm = binding.backend, binding.block_rows
    if backend == "auto":
        # TPU: the real Pallas kernel over block_rows tiles. Elsewhere
        # (CPU CI, the interpret path): the xla backend at
        # block_rows=1, where each row IS its own block — an exact
        # per-row w[gids[i]] gather for ANY slot mix, without paying the
        # interpret loop a (block, group) tile per distinct slot.
        if jax.default_backend() == "tpu":
            backend = "pallas"
        else:
            backend, bm = "xla", 1

    shape = v.shape
    d = shape[-1]
    m = 1
    for s in shape[:-1]:
        m *= int(s)
    rows = v.reshape(m, d)
    reps = m // binding.slots.shape[0]
    gids = jnp.repeat(binding.slots.astype(jnp.int32), reps)
    pad = (-m) % bm
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, d), rows.dtype)], axis=0)
        gids = jnp.concatenate(
            [gids, jnp.full((pad,), binding.num_slots, jnp.int32)], axis=0)
    h = grouped_matmul(rows.astype(a_pool.dtype), a_pool, gids,
                       block_rows=bm, backend=backend)
    out = grouped_matmul(h.astype(b_pool.dtype), b_pool, gids,
                         block_rows=bm, backend=backend)
    return out[:m].reshape(tuple(shape[:-1]) + (b_pool.shape[-1],))
