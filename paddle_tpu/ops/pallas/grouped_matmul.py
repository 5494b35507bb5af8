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

  * forward — grid (column tiles, row blocks, span). Each row block's group
    range is prefetched into SMEM; step (j, i, s) takes group gmin[i] + s,
    is SKIPPED past the block's largest id, and its weight index map is
    clamped into the block's range so that a skipped step moves no data.
    The output tile [bm, bn] accumulates over the trailing span. With the
    dispatcher's block-aligned layout span is 1 and the weights of a group
    stay in VMEM while its row blocks pass. The weight is tiled over its
    output columns ([K, bn], bn from `_col_tile`) only where one tile's
    working set does not fit the VMEM budget: every column tile streams all
    the rows again, so the rule takes the FEWEST tiles that fit, the last
    one partial where the width is no multiple of the tile.
  * dx — the forward kernel over `w` transposed (same skip structure).
  * dw — the same grid; dw[g]'s [K, bn] tile is the resident output while
    the row blocks of g pass (groups come sorted, so they follow one
    another), zeroed at the first; groups with no row block are zeroed
    outside.
  * `grouped_matmul_visit_counts` runs the range predicate as its own
    kernel so the visit counter counts what the compute kernels execute
    (mirrors `segment_block_visit_counts`).

Accumulation is fp32 (the returned array is fp32; callers cast), so bf16
inputs meet the dense-reference parity bounds; operands reach the MXU in
their own type.

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
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas._compat import x64_off as _x64_off
from paddle_tpu.ops.pallas.flash_attention import (
    _interpret_mode, _seg_blocks_can_touch, interpret_forced,
)

__all__ = ["grouped_matmul", "grouped_matmul_visit_counts",
           "expected_visit_counts", "pick_block_rows", "col_tiles"]


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
#
# Grid (column tiles, row blocks, span). Each row block's smallest and
# largest group id are prefetched as scalars, so the index maps can follow
# them: step (j, i, s) works on group gmin[i] + s and asks for THAT group's
# [K, bn] weight tile, clamped to the block's own range, so a step that has
# nothing to do names the block its neighbour had and moves no data. `span`
# is how many groups a row block may hold: 1 under the dispatcher's
# block-aligned layout (one launch a row block and column tile, no row
# mask), the number of groups for any sorted layout. Operands go to the MXU
# in their own type and accumulate in float32.
#
# The column tile: every tile streams all the laid-out rows again, so the
# kernel takes the fewest tiles whose working set fits the chip's
# `_compat.vmem_budget()`, the whole width where one does (64 MiB on a v5e:
# one tile at all six shapes of the three expert cells, the fastest count at
# each in the chip's table, PERF.md, PR 38). The tile need not divide the
# width: the last one may be partial, its columns past n never written back,
# and K (the contraction) is never tiled, so no output element changes (a
# width of 11 x 128 has no wide divisor: PERF.md, PR 38).

_VMEM_SCOPE = 16 * 2**20     # what Mosaic gives a kernel that asks for nothing


def _working_set(bm: int, k: int, bn: int, x_bytes: int, tile_bytes: int,
                 blk_bytes: int, product_rows: int) -> int:
    """VMEM bytes of a grid step: the pipeline's two buffers of the [bm, k]
    rows, of the [k, bn] weight (forward) or float32 dw tile, of the [bm, bn]
    output (forward) or dy block and of the ids, and the step's float32
    product, [bm, bn] forward and [k, bn] for dw (`product_rows`)."""
    lanes = _compat.lanes
    return (product_rows * lanes(bn) * 4
            + 2 * (bm * lanes(k) * x_bytes + k * lanes(bn) * tile_bytes
                   + bm * lanes(bn) * blk_bytes + bm * 128 * 4))


def _fwd_sizes(block_rows: int, x_dtype, w_dtype, out_dtype=jnp.float32) -> tuple:
    """`_working_set`'s bytes of the forward (and of dx, dy over w
    transposed): rows, weight tile, output block, a [block_rows, bn] product."""
    return (jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize,
            jnp.dtype(out_dtype).itemsize, block_rows)


def _dw_sizes(k: int, x_dtype, dy_dtype) -> tuple:
    """`_working_set`'s bytes of dw: rows, the float32 dw tile, the dy block,
    a [k, bn] product."""
    return (jnp.dtype(x_dtype).itemsize, 4, jnp.dtype(dy_dtype).itemsize, k)


def _col_tile(bm: int, k: int, n: int, *sizes: int) -> int:
    """Columns of a weight / dw tile: n whole when its working set fits the
    chip's `vmem_budget`, else a multiple of 128 that cuts n into the fewest
    tiles that fit (the last one may be partial); 128 when none does."""
    budget = _compat.vmem_budget()
    for tiles in range(1, pl.cdiv(n, 128) + 1):
        bn = n if tiles == 1 else _compat.lanes(pl.cdiv(n, tiles))
        if _working_set(bm, k, bn, *sizes) <= budget:
            return bn
    return min(n, 128)


def _vmem_params(working_set: int, interpret: bool) -> dict:
    """Past the compiler's own scope a call asks for its working set and a
    MiB of room (every expert cell's shape compiled so on a v5e, PR 38);
    below it, nothing."""
    if interpret or working_set + 2**20 <= _VMEM_SCOPE:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=working_set + 2**20)}


def col_tiles(block_rows: int, k: int, n: int, x_dtype, w_dtype) -> dict:
    """Column tiles of x [M, k] @ w [G, k, n] and of its two backward
    products as the kernels cut them: the forward, dx (dy in x's dtype over
    w transposed) and dw."""
    fwd = _fwd_sizes(block_rows, x_dtype, w_dtype)
    dw = _dw_sizes(k, x_dtype, x_dtype)
    return {"fwd": pl.cdiv(n, _col_tile(block_rows, k, n, *fwd)),
            "dx": pl.cdiv(k, _col_tile(block_rows, n, k, *fwd)),
            "dw": pl.cdiv(n, _col_tile(block_rows, k, n, *dw))}


def _group_of(gmin_ref, gmax_ref, i, s, num_groups):
    """(group step (i, s) works on, whether it has work, the weight / dw
    block it names)."""
    g = gmin_ref[i] + s
    needed = jnp.logical_and(g <= gmax_ref[i], g < num_groups)
    block = jnp.minimum(jnp.minimum(g, gmax_ref[i]), num_groups - 1)
    return g, needed, block


def _group_tile_map(num_groups: int):
    """Index map of a [1, K, bn] tile of the weights (forward) or of dw:
    the group step (j, i, s) works on, column tile j."""
    def index(j, i, s, gmin_ref, gmax_ref):
        return (_group_of(gmin_ref, gmax_ref, i, s, num_groups)[2], 0, j)

    return index


def _gmm_fwd_kernel(gmin_ref, gmax_ref, gid_ref, x_ref, w_ref, o_ref, *,
                    num_groups: int, span: int):
    i, s = pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g, needed, _ = _group_of(gmin_ref, gmax_ref, i, s, num_groups)

    @pl.when(needed)
    def _compute():
        x = x_ref[...]                                   # [bm, K]
        if span > 1:        # rows of other groups share the block
            x = x * (gid_ref[...] == g).astype(x.dtype)      # [bm, 1]: a column
        o_ref[...] += jax.lax.dot(x, w_ref[0],
                                  preferred_element_type=jnp.float32
                                  ).astype(o_ref.dtype)


def _gmm_dw_kernel(gmin_ref, gmax_ref, gid_ref, x_ref, dy_ref, dw_ref, *,
                   num_groups: int, span: int):
    i, s = pl.program_id(1), pl.program_id(2)
    g, needed, block = _group_of(gmin_ref, gmax_ref, i, s, num_groups)
    # the block the step before named: groups come sorted, so a group's steps
    # follow one another and its tile is zeroed when they begin
    i0 = jnp.maximum(i - 1, 0)
    before = jnp.where(s > 0,
                       _group_of(gmin_ref, gmax_ref, i, s - 1, num_groups)[2],
                       _group_of(gmin_ref, gmax_ref, i0, span - 1, num_groups)[2])
    first = jnp.logical_or(jnp.logical_and(i == 0, s == 0), before != block)

    @pl.when(jnp.logical_and(needed, first))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(needed)
    def _compute():
        x = x_ref[...]
        if span > 1:
            x = x * (gid_ref[...] == g).astype(x.dtype)      # [bm, 1]: a column
        dw_ref[0] += jax.lax.dot_general(
            x, dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _block_ranges(gids, block_rows):
    gb = gids.reshape(-1, block_rows)
    return jnp.min(gb, axis=1), jnp.max(gb, axis=1)


def _gmm_fwd_pallas(x, w, gids, block_rows, interpret, span=0,
                    out_dtype=jnp.float32):
    m, k = x.shape
    num_groups, _, n = w.shape
    span = span or num_groups
    sizes = _fwd_sizes(block_rows, x.dtype, w.dtype, out_dtype)
    bn = _col_tile(block_rows, k, n, *sizes)
    gmin, gmax = _block_ranges(gids, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(n, bn), m // block_rows, span),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda j, i, s, *_: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda j, i, s, *_: (i, 0)),
            pl.BlockSpec((1, k, bn), _group_tile_map(num_groups)),
        ],
        out_specs=pl.BlockSpec((block_rows, bn), lambda j, i, s, *_: (i, j)),
    )
    with _x64_off():
        return pl.pallas_call(
            functools.partial(_gmm_fwd_kernel, num_groups=num_groups, span=span),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            interpret=interpret,
            **_vmem_params(_working_set(block_rows, k, bn, *sizes), interpret),
            **_compat.kernel_name("grouped_matmul"),
        )(gmin, gmax, gids.reshape(m, 1), x, w)


def _gmm_dw_pallas(x, dy, gids, num_groups, block_rows, interpret, span=0):
    m, k = x.shape
    n = dy.shape[1]
    span = span or num_groups
    sizes = _dw_sizes(k, x.dtype, dy.dtype)
    bn = _col_tile(block_rows, k, n, *sizes)
    gmin, gmax = _block_ranges(gids, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(n, bn), m // block_rows, span),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda j, i, s, *_: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda j, i, s, *_: (i, 0)),
            pl.BlockSpec((block_rows, bn), lambda j, i, s, *_: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, k, bn), _group_tile_map(num_groups)),
    )
    with _x64_off():
        dw = pl.pallas_call(
            functools.partial(_gmm_dw_kernel, num_groups=num_groups, span=span),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_groups, k, n), jnp.float32),
            interpret=interpret,
            **_vmem_params(_working_set(block_rows, k, bn, *sizes), interpret),
            **_compat.kernel_name("grouped_matmul_dw"),
        )(gmin, gmax, gids.reshape(m, 1), x, dy)
    # a group no row block holds is never visited: its tile was never written
    ids = jnp.arange(num_groups, dtype=gids.dtype)[:, None]
    held = jnp.any(jnp.logical_and(gmin[None] <= ids, ids <= gmax[None]), axis=1)
    return jnp.where(held[:, None, None], dw, 0.0)


# ---------------------------------------------------------------------------
# public custom-vjp entry (pallas kernels, or the xla block-gather fallback —
# a batched matmul over w[blk_gid], exact for block-aligned layouts)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _gmm(x, w, gids, num_groups, block_rows, backend, interpret, span):
    return _gmm_forward(x, w, gids, num_groups, block_rows, backend,
                        interpret, span)


def _gmm_forward(x, w, gids, num_groups, block_rows, backend, interpret,
                 span=0, out_dtype=jnp.float32):
    """x [M, K] @ w[gids] [G, K, N] -> [M, N]. `span` is the most groups a
    row block holds (0: any sorted layout; 1: block-aligned)."""
    if backend == "pallas":
        return _gmm_fwd_pallas(x, w, gids, block_rows, interpret, span,
                               out_dtype)
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
    return y.reshape(m, w.shape[-1]).astype(out_dtype)


def _gmm_backward_dw(x, dy, gids, num_groups, block_rows, backend, interpret,
                     span=0):
    if backend == "pallas":
        return _gmm_dw_pallas(x, dy, gids, num_groups, block_rows, interpret,
                              span)
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


def _gmm_vjp_fwd(x, w, gids, num_groups, block_rows, backend, interpret,
                 span):
    y = _gmm_forward(x, w, gids, num_groups, block_rows, backend, interpret,
                     span)
    return y, (x, w, gids)


def _gmm_vjp_bwd(num_groups, block_rows, backend, interpret, span, res, dy):
    x, w, gids = res
    # dx: the SAME grouped structure over w transposed; dw: per-group
    # accumulation under the same block-skip predicate
    dyl = dy.astype(x.dtype)
    dx = _gmm_forward(dyl, jnp.swapaxes(w, 1, 2), gids,
                      num_groups, block_rows, backend, interpret, span)
    dw = _gmm_backward_dw(x, dyl, gids, num_groups, block_rows, backend,
                          interpret, span)
    dgids = np.zeros(gids.shape, jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), dgids


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def grouped_matmul(x, w, gids, *, block_rows: int | None = None,
                   backend: str | None = None, aligned: bool = False):
    """y[i] = x[i] @ w[gids[i]] over ragged, group-contiguous rows.

    x: [M, d]; w: [G, d, h]; gids: [M] int32 in [0, G] — rows with
    `gids == G` are padding and yield zero rows. M must be a multiple of
    `block_rows`. Returns fp32 [M, h] (fp32 accumulation regardless of
    input dtype). Differentiable in x and w (custom-vjp; dx/dw run the
    grouped kernels, never a dense [M, G] mask).

    Layout contract: rows sorted by id. The pallas backend masks within
    blocks, so it is exact for any sorted layout; with `aligned=True` the
    caller states that every block_rows-row block belongs to one group
    (what the MoE dispatcher emits) and the kernels take one step a block
    with no mask. The xla fallback always needs the aligned layout: it
    zeroes rows that disagree with their block's leading id.
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
                interpret, 1 if aligned else 0)


# ---------------------------------------------------------------------------
# visit-count kernel (the tests' counter)
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
    sum()/ (blocks * G) is the visited fraction of the tiles.
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
    tests assert against the kernel counter."""
    g = np.asarray(gids, np.int32).reshape(-1, block_rows)
    gmin = g.min(axis=1)[:, None]
    gmax = g.max(axis=1)[:, None]
    gs = np.arange(num_groups, dtype=np.int32)[None, :]
    return np.logical_and(gs <= gmax, gs >= gmin).sum(axis=1).astype(np.int32)
