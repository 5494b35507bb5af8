"""The two row movers of an expert layer's layout, each the other's transpose.

A layout (`RowsLayout`, built once a layer and step by `rows_layout`) is a
few integer arrays: `row_pair [M]` says which token-expert pair
(token * k + j) a row of the experts' buffer holds, `pair_row [N, k]` in
which buffer row a pair sits; a row no pair fell into holds N * k, a pair
that did not land here M. The rows that hold pairs fill the first
`live_blocks` blocks of `block_rows` rows. `tok_pair` / `tok_row [C]` list
the pairs that landed here, and their rows, in token order, and `ends` says
where each block of tokens stops in that list.

  * `rows_gather`:  buf[r] = src[row_pair[r] // k], zero where no pair;
  * `rows_combine`: out[t] = sum_j w[t, j] * y[pair_row[t, j]], in float32.

`rows_gather`'s backward is `rows_combine` with weights of one, and
`rows_combine`'s backward is the gather, scaled by each row's weight, which
also takes the weights' gradient (a dot product of two rows it holds).

Kernels (`moe_rows_gather`, `moe_rows_combine`): a grid over row blocks
(token blocks for the combine); a row is fetched by a copy of the 8 rows of
its HBM tile (the least a row-sliced copy may move: Mosaic refuses a slice of
one row of a tiled array) and picked out of the tile in VMEM (a bfloat16
tile through its 32-bit view, two rows a word); a step starts the copies of
the NEXT block before it works on its own, so a block's copies are all in
flight while the block before it is computed. The combine walks the
token-ordered list, so neither kernel looks at a pair that is not here. A row
block at or past `live_blocks` costs one grid step and moves nothing: its
rows of the buffer are NOT written, which the grouped kernels allow (they
compute on no block whose ids are past the groups) and the `xla` form does
not need (it fills them with zeros).

Backends as `grouped_matmul`'s: `pallas` on a TPU and under
`force_interpret()`, `xla` elsewhere (one `take` with `mode="fill"`; the
k-way gather-and-sum), and `xla` for a shape the kernels are not written for
(`rows_backend`). The gather of a 16-bit source is the `take` on either
backend: XLA's row copies are not bound to a tile, and move the rows laid
out as fast as the kernel moves the tiles of the pairs (PERF.md, PR 34).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas.flash_attention import _interpret_mode
from paddle_tpu.ops.pallas.grouped_matmul import _resolve_backend

__all__ = ["RowsLayout", "rows_layout", "rows_gather", "rows_combine",
           "rows_backend", "rows_gather_visit_counts",
           "rows_combine_visit_counts", "expected_gather_visits",
           "expected_combine_visits"]

GROUP = 8             # rows of a 32-bit HBM tile
_SLOTS = 128          # most pairs of a combine step
_SMEM = 768 * 2**10   # of the core's 1 MB, for the prefetched index arrays


class RowsLayout(NamedTuple):
    row_pair: jax.Array      # [M] pair a buffer row holds, or N * k
    pair_row: jax.Array      # [N, k] buffer row of a pair, or M
    tok_pair: jax.Array      # [C] the pairs that landed here, by token; N * k past them
    tok_row: jax.Array       # [C] their buffer rows; M past them
    ends: jax.Array          # [N / token_block(k)] where a token block stops in tok_*
    live_blocks: jax.Array   # [] leading row blocks that hold pairs


def token_block(k: int) -> int:
    """Tokens of a combine step: `_SLOTS` pairs, in whole bfloat16 tiles."""
    return max(16, (_SLOTS // k) // 16 * 16)


def rows_layout(row_pair, pair_row, live_blocks, cap: int) -> RowsLayout:
    """The layout of `row_pair` / `pair_row` with the token-ordered list of
    the at most `cap` pairs that landed here: one cumsum and one scatter."""
    n, k = pair_row.shape
    (m,), nk = row_pair.shape, n * k
    flat = pair_row.reshape(-1)
    here = flat < m
    upto = jnp.cumsum(here.astype(jnp.int32))
    tok_pair = jnp.full((cap,), nk, jnp.int32).at[
        jnp.where(here, upto - 1, cap)].set(jnp.arange(nk, dtype=jnp.int32), mode="drop")
    tok_row = jnp.take(flat, tok_pair, mode="fill", fill_value=m)
    bt = token_block(k)
    ends = upto.reshape(-1, bt * k)[:, -1] if n % bt == 0 else upto[-1:]
    return RowsLayout(row_pair, pair_row, tok_pair, tok_row, ends,
                      jnp.asarray(live_blocks, jnp.int32))


def rows_backend(backend, n: int, d: int, k: int, dtype, m: int = 0,
                 cap: int = 0) -> str:
    """The backend both movers of a layer take: the one asked for (or the
    `moe_gmm_backend` flag), `xla` where the kernels do not apply: rows of
    whole lanes in float32 or bfloat16, tokens in whole blocks, index arrays
    (`m` buffer rows, `cap` pairs) that fit the scalar memory."""
    backend = _resolve_backend(backend)
    fits = (jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
            and d % 128 == 0 and n % token_block(k) == 0
            and 4 * max(m, 3 * cap + n // token_block(k)) <= _SMEM)
    return backend if fits else "xla"


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _div(a, b: int):
    """a // b of a non-negative index (`//` lowers through `sign`, which the
    kernel's late lowering sees in 64 bits)."""
    return jax.lax.div(a, jnp.asarray(b, a.dtype))


def _row_of_tile(tile_ref, row):
    """float32 [1, d]: row `row & 7` of a staged tile. A bfloat16 tile is read
    through its 32-bit view, rows 2j and 2j + 1 the halves of word row j, and
    widened exactly (a bfloat16 is the high half of its float32)."""
    sub = row & (GROUP - 1)
    if tile_ref.dtype == jnp.float32:
        return tile_ref[pl.ds(sub, 1), :]
    words = tile_ref.bitcast(jnp.uint32)[pl.ds(sub >> 1, 1), :]
    bits = jnp.where((sub & 1) == 1, words & jnp.uint32(0xFFFF0000), words << 16)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _start_tiles(row_of, lo, hi, src_ref, stage, sem):
    """Start a copy of the tile of source row `row_of(c)` into slot c - lo of
    `stage` for every c in [lo, hi) whose `row_of` is not negative; returns
    how many. With `src_ref` None only counts (the visit-count twins)."""
    def start(c, n):
        row = row_of(c)
        if src_ref is not None:
            @pl.when(row >= 0)
            def _():
                first = pl.multiple_of(row & -GROUP, GROUP)
                pltpu.make_async_copy(src_ref.at[pl.ds(first, GROUP)],
                                      stage.at[c - lo], sem).start()
        return n + (row >= 0).astype(jnp.int32)

    return jax.lax.fori_loop(lo, hi, start, jnp.int32(0))


def _wait_tiles(n, src_ref, stage, sem):
    def wait(_, c):
        pltpu.make_async_copy(src_ref.at[pl.ds(0, GROUP)], stage.at[0], sem).wait()
        return c
    jax.lax.fori_loop(0, n, wait, 0)


def _a_step_ahead(i, steps, start, work):
    """Step i of a grid whose first `steps` steps have work: start the
    copies of step i + 1 into the other half of the stage (and, at step 0,
    step 0's own), then do step i's work on the half filled a step ago."""
    @pl.when(jnp.logical_and(i == 0, steps > 0))
    def _():
        start(i, 0)

    @pl.when(i + 1 < steps)
    def _():
        start(i + 1, (i + 1) & 1)

    @pl.when(i < steps)
    def _():
        work(i & 1)


def _gather_row(rp_ref, r, k, n_pairs):
    """The source row of buffer row r, -1 where no pair fell into it."""
    p = rp_ref[r]
    return jnp.where(p < n_pairs, _div(p, k), -1)


def _gather_kernel(live_ref, rp_ref, col_ref, *refs, k, n_pairs, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    y_ref = refs.pop(0) if dotted else None
    src_ref, o_ref = refs.pop(0), refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    stage, rows_ref, sems, count_ref = refs
    bm = o_ref.shape[0]
    i = pl.program_id(0)
    row_of = functools.partial(_gather_row, rp_ref, k=k, n_pairs=n_pairs)

    def start(b, half):
        count_ref[half] = _start_tiles(row_of, b * bm, (b + 1) * bm, src_ref,
                                       stage.at[half], sems.at[half])

    def work(half):
        _wait_tiles(count_ref[half], src_ref, stage.at[half], sems.at[half])

        def pick(r, c):
            row = row_of(i * bm + r)

            @pl.when(row >= 0)
            def _():
                rows_ref[pl.ds(r, 1), :] = _row_of_tile(stage.at[half, r], row)
            return c

        jax.lax.fori_loop(0, bm, pick, 0)
        # a row no pair fell into keeps what the block before left there
        rows = jnp.where(col_ref[...] < n_pairs, rows_ref[...], 0.0)
        if dotted:
            dot_ref[...] = jnp.sum(rows * y_ref[...], axis=1, keepdims=True)
        if scaled:
            rows = rows * scale_ref[...]
        o_ref[...] = rows.astype(o_ref.dtype)

    _a_step_ahead(i, live_ref[0], start, work)


def _token_range(ends_ref, i):
    """[lo, hi) of token block i in the token-ordered list."""
    return jnp.where(i > 0, ends_ref[jnp.maximum(i - 1, 0)], 0), ends_ref[i]


def _combine_kernel(ends_ref, tp_ref, tr_ref, *refs, k, weighted):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    y_ref, o_ref, stage, acc_ref, sems = refs
    bt = o_ref.shape[0]
    i = pl.program_id(0)

    def start(b, half):
        lo, hi = _token_range(ends_ref, b)
        _start_tiles(lambda c: tr_ref[c], lo, hi, y_ref, stage.at[half],
                     sems.at[half])

    def work(half):
        lo, hi = _token_range(ends_ref, i)
        _wait_tiles(hi - lo, y_ref, stage.at[half], sems.at[half])
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def add(c, carry):
            row = _row_of_tile(stage.at[half, c - lo], tr_ref[c])
            if weighted:
                row = row * w_ref[c]
            acc_ref[pl.ds(_div(tp_ref[c], k) - i * bt, 1), :] += row
            return carry

        jax.lax.fori_loop(lo, hi, add, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    _a_step_ahead(i, pl.num_programs(0), start, work)


def _count_gather_kernel(live_ref, rp_ref, o_ref, *, bm, k, n_pairs):
    i = pl.program_id(0)
    n = _start_tiles(functools.partial(_gather_row, rp_ref, k=k, n_pairs=n_pairs),
                     i * bm, (i + 1) * bm, None, None, None)
    o_ref[...] = jnp.full((1, 1), jnp.where(i < live_ref[0], n, 0), jnp.float32)


def _count_combine_kernel(ends_ref, tr_ref, o_ref):
    lo, hi = _token_range(ends_ref, pl.program_id(0))
    n = _start_tiles(lambda c: tr_ref[c], lo, hi, None, None, None)
    o_ref[...] = jnp.full((1, 1), n, jnp.float32)


def _live_map(i, live_ref, *_):
    """Index map of a gather operand's block i: the last block that holds
    pairs once i is past them, so that such a step moves nothing."""
    return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0)), 0


def _params(interpret):
    if interpret:
        return {}
    # both kernels stage two blocks' tiles: 8-19 MB of the budget
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_compat.vmem_budget())}


def _stage(slots, src):
    """Scratch of a kernel that works a step behind its copies: two halves
    of `slots` tiles of `src`, a semaphore each."""
    return [pltpu.VMEM((2, slots, GROUP, src.shape[1]), src.dtype)], [
        pltpu.SemaphoreType.DMA((2,))]


def _gather_pallas(src, lay, scale, y, block_rows, interpret):
    n, d = src.shape
    (m,), k, bm = lay.row_pair.shape, lay.pair_row.shape[1], block_rows
    col = pl.BlockSpec((bm, 1), _live_map)
    ops, specs = [lay.row_pair.reshape(m, 1)], [col]
    if scale is not None:
        ops.append(scale.astype(jnp.float32).reshape(m, 1))
        specs.append(col)
    if y is not None:
        ops.append(y.astype(jnp.float32))
        specs.append(pl.BlockSpec((bm, d), _live_map))
    ops.append(src)
    specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_shape = [jax.ShapeDtypeStruct((m, d), src.dtype)]
    out_specs = [pl.BlockSpec((bm, d), _live_map)]
    if y is not None:
        out_shape.append(jax.ShapeDtypeStruct((m, 1), jnp.float32))
        out_specs.append(col)
    stage, sems = _stage(bm, src)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // bm,), in_specs=specs,
        out_specs=out_specs,
        scratch_shapes=stage + [pltpu.VMEM((bm, d), jnp.float32)]
        + sems + [pltpu.SMEM((2,), jnp.int32)])
    with _compat.kernel_trace_ctx(interpret):
        out = pl.pallas_call(
            functools.partial(_gather_kernel, k=k, n_pairs=n * k,
                              scaled=scale is not None, dotted=y is not None),
            grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
            **_params(interpret), **_compat.kernel_name("moe_rows_gather"),
        )(lay.live_blocks.reshape(1), lay.row_pair, *ops)
    return out[0], (out[1][:, 0] if y is not None else None)


def _combine_pallas(y, w, lay, interpret):
    (n, k), d = lay.pair_row.shape, y.shape[1]
    bt = token_block(k)
    scalars = [lay.ends, lay.tok_pair, lay.tok_row]
    if w is not None:
        scalars.append(jnp.take(w.astype(jnp.float32).reshape(-1), lay.tok_pair,
                                mode="fill", fill_value=0))
    stage, sems = _stage(bt * k, y)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(n // bt,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bt, d), lambda i, *_: (i, 0)),
        scratch_shapes=stage + [pltpu.VMEM((bt, d), jnp.float32)] + sems)
    with _compat.kernel_trace_ctx(interpret):
        return pl.pallas_call(
            functools.partial(_combine_kernel, k=k, weighted=w is not None),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
            interpret=interpret, **_params(interpret),
            **_compat.kernel_name("moe_rows_combine"),
        )(*scalars, y)


# ---------------------------------------------------------------------------
# the two movers on either backend, and their custom-vjp entries
# ---------------------------------------------------------------------------

def _gather_rows(src, lay, scale, y, block_rows, backend, interpret):
    """(buf [M, d] = scale * src[row_pair // k], zero where no pair;
    [M] dot products of the unscaled rows with `y`'s, or None)."""
    if backend == "pallas" and src.dtype == jnp.float32:
        return _gather_pallas(src, lay, scale, y, block_rows, interpret)
    rows = jnp.take(src, lay.row_pair // lay.pair_row.shape[1], axis=0,
                    mode="fill", fill_value=0)
    dot = None
    if y is not None:
        dot = jnp.sum(rows.astype(jnp.float32) * y, axis=1)
    if scale is not None:
        rows = (rows.astype(jnp.float32) * scale[:, None]).astype(src.dtype)
    return rows, dot


def _combine_rows(y, w, lay, backend, interpret):
    """out [N, d] = sum_j w[:, j] * y[pair_row[:, j]] (weights of one where
    `w` is None), summed in float32, in y's type."""
    if backend == "pallas":
        return _combine_pallas(y, w, lay, interpret)
    n, k = lay.pair_row.shape
    out = jnp.zeros((n, y.shape[1]), jnp.float32)
    for j in range(k):
        rows = jnp.take(y, lay.pair_row[:, j], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        out += rows if w is None else rows * w[:, j, None].astype(jnp.float32)
    return out.astype(y.dtype)


def _no_grad(lay):
    return jax.tree.map(lambda a: np.zeros(a.shape, jax.dtypes.float0), lay)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gather(src, lay, block_rows, backend, interpret):
    return _gather_rows(src, lay, None, None, block_rows, backend, interpret)[0]


def _gather_fwd(src, lay, block_rows, backend, interpret):
    return _gather_rows(src, lay, None, None, block_rows, backend, interpret)[0], lay


def _gather_bwd(block_rows, backend, interpret, lay, d_buf):
    return _combine_rows(d_buf, None, lay, backend, interpret), _no_grad(lay)


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _combine(y, w, lay, block_rows, backend, interpret):
    return _combine_rows(y, w, lay, backend, interpret)


def _combine_fwd(y, w, lay, block_rows, backend, interpret):
    return _combine_rows(y, w, lay, backend, interpret), (y, w, lay)


def _combine_bwd(block_rows, backend, interpret, res, d_out):
    y, w, lay = res
    w_row = jnp.take(w.reshape(-1), lay.row_pair, mode="fill", fill_value=0)
    d_y, dot = _gather_rows(d_out, lay, w_row, y, block_rows, backend, interpret)
    d_w = jnp.take(dot, lay.pair_row, mode="fill", fill_value=0).astype(w.dtype)
    return d_y, d_w, _no_grad(lay)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _entry(backend, src, lay):
    backend = rows_backend(backend, lay.pair_row.shape[0], src.shape[1],
                           lay.pair_row.shape[1], src.dtype,
                           lay.row_pair.shape[0], lay.tok_pair.shape[0])
    return backend, (_interpret_mode() if backend == "pallas" else False)


def rows_gather(src, layout: RowsLayout, *, block_rows: int,
                backend: str | None = None):
    """buf [M, d]: row r holds `src[row_pair[r] // k]`, zeros where
    `row_pair[r]` is N * k (no pair). Blocks of `block_rows` rows at or past
    `live_blocks` are left unwritten by the kernel (float32 sources; zeros
    by the `take`). Differentiable in `src`: the backward is `rows_combine`
    of the cotangent with weights of one."""
    return _gather(src, layout, block_rows, *_entry(backend, src, layout))


def rows_combine(y, w, layout: RowsLayout, *, block_rows: int,
                 backend: str | None = None):
    """out [N, d] in y's type: `sum_j w[t, j] * y[pair_row[t, j]]` over the
    pairs whose `pair_row` is under M, summed in float32. Differentiable in
    `y` (the backward is the gather of the cotangent's rows, each scaled by
    its pair's weight) and in `w` (that row's dot product with y's)."""
    return _combine(y, w, layout, block_rows, *_entry(backend, y, layout))


# ---------------------------------------------------------------------------
# visit counts (the tests' counters): the kernels' own predicates, run alone
# ---------------------------------------------------------------------------

def _count_call(kernel, name, steps, scalars, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(steps,), in_specs=[],
        out_specs=pl.BlockSpec((1, 1), lambda i, *_: (i, 0)))
    interpret = _interpret_mode() if interpret is None else interpret
    with _compat.kernel_trace_ctx(interpret):
        cnt = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((steps, 1), jnp.float32),
            interpret=interpret, **_compat.kernel_name(name),
        )(*(jnp.asarray(a, jnp.int32) for a in scalars))
    return cnt[:, 0].astype(jnp.int32)


def rows_gather_visit_counts(layout: RowsLayout, block_rows: int,
                             interpret: bool | None = None):
    """int32 [M // block_rows]: the rows `moe_rows_gather` fetches in each
    row block, by the kernel's own predicates; 0 for a block it skips."""
    n, k = layout.pair_row.shape
    return _count_call(
        functools.partial(_count_gather_kernel, bm=block_rows, k=k, n_pairs=n * k),
        "moe_rows_gather_block_count", layout.row_pair.shape[0] // block_rows,
        (layout.live_blocks.reshape(1), layout.row_pair), interpret)


def rows_combine_visit_counts(layout: RowsLayout, interpret: bool | None = None):
    """int32 [N // token_block(k)]: the rows `moe_rows_combine` fetches in
    each token block."""
    return _count_call(_count_combine_kernel, "moe_rows_combine_block_count",
                       layout.ends.shape[0], (layout.ends, layout.tok_row),
                       interpret)


def expected_gather_visits(layout: RowsLayout, block_rows: int):
    """The same counts in plain numpy."""
    held = (np.asarray(layout.row_pair).reshape(-1, block_rows)
            < layout.pair_row.size).sum(axis=1)
    live = int(layout.live_blocks)
    return np.where(np.arange(held.size) < live, held, 0).astype(np.int32)


def expected_combine_visits(layout: RowsLayout):
    n, k = layout.pair_row.shape
    here = np.asarray(layout.pair_row) < layout.row_pair.shape[0]
    return here.reshape(n // token_block(k), -1).sum(axis=1).astype(np.int32)
