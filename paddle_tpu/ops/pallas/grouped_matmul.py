"""Pallas grouped (ragged) matmul over expert buckets — the dropless-MoE
compute primitive.

Reference analog: the reference MoE stack runs each expert's FFN over a
fixed-capacity `[E, C, d]` bucket tensor (incubate/distributed/models/moe),
padding to capacity and dropping overflow. Here the buckets are RAGGED: rows
arrive grouped by expert id (`gids`, non-decreasing within the dispatch
layout the MoE dispatcher emits) and each expert's matmul runs over exactly
its rows — O(actual tokens), not O(E*C).

Kernel design (the PR-5/PR-9 ragged-block pattern, expert buckets as one
more segment vocabulary):

  * forward — grid (row_blocks, G). The output tile [bm, h] for row block i
    accumulates over the trailing (sequential on TPU) group dim; a group g
    is SKIPPED for row block i unless g intersects the block's group-id
    range — the SAME `_seg_blocks_can_touch` predicate the flash/paged
    attention kernels use for packed-segment block skipping. With the
    dispatcher's block-aligned layout each row block matches exactly one
    group, so the kernel visits (row_blocks) of (row_blocks*G) tiles.
  * dx — the forward kernel over `w` transposed (same skip structure).
  * dw — grid (G, row_blocks): dw[g] accumulates masked x_blk^T @ dy_blk
    across the trailing row-block dim under the same predicate.
  * `grouped_matmul_visit_counts` runs the predicate as its own kernel so
    the bench counter provably counts what the compute kernels execute
    (mirrors `segment_block_visit_counts`).

Accumulation is fp32 (the returned array is fp32; callers cast), so bf16
inputs meet the dense-reference parity bounds.

Backends: `pallas` (TPU, or interpret mode under `force_interpret()` so
tier-1 CPU tests exercise the exact kernel code), and an `xla` fallback —
a block-gather batched matmul (`w[blk_gid]` per row block) that is exact
for BLOCK-ALIGNED layouts (every bm-row block holds rows of one group,
which is what the dispatcher guarantees; rows disagreeing with their
block's leading group id contribute zero). `auto` picks pallas on TPU /
forced-interpret and xla elsewhere.

Rows with `gids == num_groups` are padding/overflow ("trash") rows: no
kernel tile ever matches them, so their output rows stay zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas._compat import x64_off as _x64_off
from paddle_tpu.ops.pallas.flash_attention import (
    _interpret_mode, _seg_blocks_can_touch, interpret_forced,
)

__all__ = ["grouped_matmul", "grouped_matmul_visit_counts",
           "expected_visit_counts", "pick_block_rows"]


def _heuristic_block_rows(n_rows: int, num_groups: int) -> int:
    for bm in (128, 32, 8):
        if n_rows >= bm * max(num_groups, 1):
            return bm
    return 8


def pick_block_rows(n_rows: int, num_groups: int) -> int:
    """Rows per grid block, through the shared tuning resolver:
    FLAGS_moe_block_rows override > tuned entry > heuristic (128 —
    MXU-friendly — when buckets are large enough that per-group alignment
    padding stays small, stepping down for tiny problems)."""
    from paddle_tpu.tuning.blocks import resolve_blocks

    res = resolve_blocks(
        "grouped_matmul", {"n_rows": n_rows, "num_groups": num_groups},
        default=lambda g: (_heuristic_block_rows(n_rows, num_groups),))
    return res.values["block_rows"]


def _resolve_backend(backend: str | None) -> str:
    from paddle_tpu.core.flags import flag

    backend = backend or flag("moe_gmm_backend")
    if backend == "auto":
        if interpret_forced():
            return "pallas"
        return "pallas" if _compat.on_tpu() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"moe_gmm_backend={backend!r}: auto|pallas|xla")
    return backend


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def _gmm_fwd_kernel(gid_ref, x_ref, w_ref, o_ref):
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gid = gid_ref[0]                                     # [bm] int32
    needed = _seg_blocks_can_touch(jnp.min(gid), jnp.max(gid), g, g)

    @pl.when(needed)
    def _compute():
        x = x_ref[...].astype(jnp.float32)               # [bm, d]
        w = w_ref[0].astype(jnp.float32)                 # [d, h]
        mask = (gid == g).astype(jnp.float32)[:, None]
        o_ref[...] += jax.lax.dot(x * mask, w,
                                  preferred_element_type=jnp.float32)


def _gmm_dw_kernel(gid_ref, x_ref, dy_ref, dw_ref):
    g = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    gid = gid_ref[0]
    needed = _seg_blocks_can_touch(jnp.min(gid), jnp.max(gid), g, g)

    @pl.when(needed)
    def _compute():
        x = x_ref[...].astype(jnp.float32)               # [bm, d]
        dy = dy_ref[...].astype(jnp.float32)             # [bm, h]
        mask = (gid == g).astype(jnp.float32)[:, None]
        dw_ref[0] += jax.lax.dot((x * mask).T, dy,
                                 preferred_element_type=jnp.float32)


def _gmm_fwd_pallas(x, w, gids, block_rows, interpret):
    m, d = x.shape
    num_groups, _, h = w.shape
    gid2 = gids.reshape(1, m)
    with _x64_off():
        return pl.pallas_call(
            _gmm_fwd_kernel,
            grid=(m // block_rows, num_groups),
            in_specs=[
                pl.BlockSpec((1, block_rows), lambda i, g: (0, i)),
                pl.BlockSpec((block_rows, d), lambda i, g: (i, 0)),
                pl.BlockSpec((1, d, h), lambda i, g: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, h), lambda i, g: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, h), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("grouped_matmul"),
        )(gid2, x, w)


def _gmm_dw_pallas(x, dy, gids, num_groups, block_rows, interpret):
    m, d = x.shape
    h = dy.shape[1]
    gid2 = gids.reshape(1, m)
    with _x64_off():
        return pl.pallas_call(
            _gmm_dw_kernel,
            grid=(num_groups, m // block_rows),
            in_specs=[
                pl.BlockSpec((1, block_rows), lambda g, i: (0, i)),
                pl.BlockSpec((block_rows, d), lambda g, i: (i, 0)),
                pl.BlockSpec((block_rows, h), lambda g, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, d, h), lambda g, i: (g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((num_groups, d, h), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("grouped_matmul_dw"),
        )(gid2, x, dy)


# ---------------------------------------------------------------------------
# public custom-vjp entry (pallas kernels, or the xla block-gather fallback —
# a batched matmul over w[blk_gid], exact for block-aligned layouts)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(x, w, gids, num_groups, block_rows, backend, interpret):
    return _gmm_forward(x, w, gids, num_groups, block_rows, backend,
                        interpret)


def _gmm_forward(x, w, gids, num_groups, block_rows, backend, interpret):
    if backend == "pallas":
        return _gmm_fwd_pallas(x, w, gids, block_rows, interpret)
    m, d = x.shape
    bm = block_rows
    xb = x.reshape(m // bm, bm, d)
    gb = gids.reshape(m // bm, bm)
    blk_g = gb[:, 0]
    wb = jnp.take(w, jnp.clip(blk_g, 0, num_groups - 1), axis=0)
    mask = jnp.logical_and(gb == blk_g[:, None], gb < num_groups)
    xm = xb.astype(jnp.float32) * mask.astype(jnp.float32)[..., None]
    y = jnp.einsum("bmd,bdh->bmh", xm, wb.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return y.reshape(m, w.shape[-1])


def _gmm_backward_dw(x, dy, gids, num_groups, block_rows, backend, interpret):
    if backend == "pallas":
        return _gmm_dw_pallas(x, dy, gids, num_groups, block_rows, interpret)
    m, d = x.shape
    h = dy.shape[1]
    bm = block_rows
    xb = x.reshape(m // bm, bm, d)
    gb = gids.reshape(m // bm, bm)
    blk_g = gb[:, 0]
    mask = jnp.logical_and(gb == blk_g[:, None], gb < num_groups)
    xm = xb.astype(jnp.float32) * mask.astype(jnp.float32)[..., None]
    per_block = jnp.einsum("bmd,bmh->bdh", xm,
                           dy.reshape(m // bm, bm, h).astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    return jnp.zeros((num_groups, d, h), jnp.float32).at[
        jnp.clip(blk_g, 0, num_groups - 1)].add(
        per_block * (blk_g < num_groups).astype(jnp.float32)[:, None, None])


def _gmm_vjp_fwd(x, w, gids, num_groups, block_rows, backend, interpret):
    y = _gmm_forward(x, w, gids, num_groups, block_rows, backend, interpret)
    return y, (x, w, gids)


def _gmm_vjp_bwd(num_groups, block_rows, backend, interpret, res, dy):
    x, w, gids = res
    # dx: the SAME grouped structure over w transposed; dw: per-group
    # accumulation under the same block-skip predicate
    dx = _gmm_forward(dy, jnp.swapaxes(w, 1, 2).astype(jnp.float32), gids,
                      num_groups, block_rows, backend, interpret)
    dw = _gmm_backward_dw(x, dy, gids, num_groups, block_rows, backend,
                          interpret)
    dgids = np.zeros(gids.shape, jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), dgids


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def grouped_matmul(x, w, gids, *, block_rows: int | None = None,
                   backend: str | None = None):
    """y[i] = x[i] @ w[gids[i]] over ragged, group-contiguous rows.

    x: [M, d]; w: [G, d, h]; gids: [M] int32 in [0, G] — rows with
    `gids == G` are padding and yield zero rows. M must be a multiple of
    `block_rows`. Returns fp32 [M, h] (fp32 accumulation regardless of
    input dtype). Differentiable in x and w (custom-vjp; dx/dw run the
    grouped kernels, never a dense [M, G] mask).

    Layout contract: rows grouped by id with each block_rows-row block
    belonging to one group (what the MoE dispatcher emits). The pallas
    backend additionally masks within blocks, so it is exact for any
    grouped layout; the xla fallback zeroes rows that disagree with their
    block's leading id.
    """
    m, d = x.shape
    num_groups = w.shape[0]
    if gids.shape != (m,):
        raise ValueError(f"gids shape {gids.shape} != ({m},)")
    bm = block_rows or pick_block_rows(m, num_groups)
    if m % bm:
        # Surface the bad launch config here with its provenance — without
        # this check it dies inside Pallas grid setup with an opaque shape
        # error (the flash-attention block-validation idiom from PR-5).
        if block_rows is not None:
            src = f"block_rows={block_rows} (caller-supplied)"
        else:
            from paddle_tpu.tuning.blocks import last_resolution

            res = last_resolution("grouped_matmul")
            prov = res.provenance if res is not None else "default"
            detail = {"flag": "FLAGS_moe_block_rows override",
                      "tuned": "tuning-cache entry",
                      "default": "auto-picked"}.get(prov, prov)
            src = f"block_rows={bm} ({detail})"
        raise ValueError(
            f"grouped_matmul: rows {m} not a multiple of {src}; pad the "
            f"row count to a multiple of the block, or set "
            f"FLAGS_moe_block_rows to a divisor of {m}")
    backend = _resolve_backend(backend)
    interpret = _interpret_mode() if backend == "pallas" else False
    return _gmm(x, w, gids.astype(jnp.int32), num_groups, bm, backend,
                interpret)


# ---------------------------------------------------------------------------
# visit-count kernel (the bench counter)
# ---------------------------------------------------------------------------

def _visit_kernel(gid_ref, o_ref, *, num_groups: int):
    gid = gid_ref[0]
    gmin = jnp.min(gid)
    gmax = jnp.max(gid)
    gs = jax.lax.broadcasted_iota(jnp.int32, (1, num_groups), 1)
    visited = _seg_blocks_can_touch(gmin, gmax, gs, gs)
    o_ref[...] = jnp.sum(visited.astype(jnp.float32)).reshape(1, 1)


def grouped_matmul_visit_counts(gids, num_groups: int, block_rows: int,
                                interpret: bool | None = None):
    """Per-row-block count of groups the grouped-matmul kernels VISIT,
    computed by running the forward kernel's exact `_seg_blocks_can_touch`
    predicate as its own Pallas kernel (mirror of
    `segment_block_visit_counts`). int32 [M // block_rows];
    sum()/ (blocks * G) is the visited fraction the MOE bench arm reports.
    Padding rows (`gids == num_groups`) never match any group."""
    gids = jnp.asarray(gids, jnp.int32)
    (m,) = gids.shape
    if interpret is None:
        interpret = _interpret_mode()
    kernel = functools.partial(_visit_kernel, num_groups=num_groups)
    with _x64_off():
        cnt = pl.pallas_call(
            kernel,
            grid=(m // block_rows,),
            in_specs=[pl.BlockSpec((1, block_rows), lambda i: (0, i))],
            out_specs=pl.BlockSpec((1, 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m // block_rows, 1), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("grouped_matmul_block_count"),
        )(gids.reshape(1, m))
    return cnt[:, 0].astype(jnp.int32)


def expected_visit_counts(gids, num_groups: int, block_rows: int):
    """The same predicate evaluated in plain numpy — the cross-check the
    bench asserts against the kernel counter."""
    g = np.asarray(gids, np.int32).reshape(-1, block_rows)
    gmin = g.min(axis=1)[:, None]
    gmax = g.max(axis=1)[:, None]
    gs = np.arange(num_groups, dtype=np.int32)[None, :]
    return np.logical_and(gs <= gmax, gs >= gmin).sum(axis=1).astype(np.int32)
