"""Dropless (capacity-free) expert-parallel MoE dispatch.

Reference context: the reference MoE layer (moe_layer.py:263) and the
capacity-bucketed TPU port (`_sparse_moe`) both bound each expert at C
slots — padding wastes FLOPs at low load, overflow tokens are silently
dropped at high load. This module removes the capacity entirely:

  * **sort-based ragged dispatch** — token copies are argsorted by expert
    id into contiguous buckets; per-expert offsets come from a `cumsum` of
    counts. Every shape is STATIC ([N*k] permutations, a [M, d] bucket
    buffer with M = align(N*k) + E*block padding), so varying expert loads
    never retrace. Bucket starts are aligned to the grouped-matmul block
    size, so every row block belongs to exactly one expert.
  * **grouped expert FFN** — `ops.pallas.grouped_matmul` runs each
    expert's two matmuls over exactly its rows, skipping (row-block,
    expert) tiles via the shared `_seg_blocks_can_touch` predicate.
  * **fused permute→expert→unpermute** — scatter, grouped FFN and the
    combining gather live in ONE traced body (one program under jit /
    shard_map); the gate-weight combine runs in fp32.
  * **expert parallelism** — under an `ep` mesh axis the aligned buckets
    ride `lax.all_to_all` to the expert owners grouped per destination
    (each rank's slice stays block-aligned, so the receiver feeds the
    grouped kernel directly — no re-sort). The a2a payload is worst-case
    sized ([ep, align(N*k)+El*block, d]): static shapes are what XLA
    needs, and `jax.lax.ragged_all_to_all` (newer JAX) is the drop-in
    shrink once available.
  * **a2a/compute overlap** — the optional shared-expert (dense) branch is
    computed BETWEEN the dispatch and combine all_to_alls inside the same
    shard_map body, with no data dependence on either, so XLA's
    latency-hiding scheduler overlaps it with the ICI transfers.
  * **routing** — token-choice (the `_route` gate semantics: naive top-k,
    GShard random second-expert, Switch jitter; gate-level capacity is
    ignored — nothing drops) and expert-choice (each expert picks its
    top-C tokens, C = k*N/E block-aligned: perfectly balanced by
    construction, tokens may be picked by 0..E experts).

Both bodies return ``(out [N,d], l_aux, dropped, counts [E])`` — the same
contract as `_sparse_moe` (dropped is identically 0 here; counts feed the
per-expert load telemetry).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul, pick_block_rows,
)

__all__ = ["_dropless_moe", "_expert_choice_moe", "ragged_layout", "pair_rows"]


def _round_up(v, m):
    return ((v + m - 1) // m) * m


def _place_by_group(gids_all, groups):
    """(each copy's position in a stable sort by group id, copies of each
    group [groups]). With few groups (a chip's share of the experts and the
    trash) without sorting or scattering: a copy's place is its group's start
    plus how many of its group came before it. A stable sort of 131,072 keys
    takes 36 s to compile for a v5e, this takes 1 (XLA analysis, PR 27)."""
    (Nk,) = gids_all.shape
    if groups > 16:
        counts = jnp.zeros((groups,), jnp.int32).at[gids_all].add(1)
        return jnp.zeros((Nk,), jnp.int32).at[jnp.argsort(gids_all)].set(
            jnp.arange(Nk, dtype=jnp.int32)), counts
    onehot = (gids_all[:, None] == jnp.arange(groups, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)                                 # [Nk, groups]
    before = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    counts = jnp.sum(onehot, axis=0)
    return jnp.take(jnp.cumsum(counts) - counts, gids_all) + before, counts


def _order_by_group(gids_all, counts_full):
    """What a stable `argsort(gids_all)` gives: with few groups
    `_place_by_group`'s permutation, inverted by one scatter."""
    (Nk,), groups = gids_all.shape, counts_full.shape[0]
    if groups > 16:
        return jnp.argsort(gids_all)                              # stable
    return jnp.zeros((Nk,), jnp.int32).at[
        _place_by_group(gids_all, groups)[0]].set(jnp.arange(Nk, dtype=jnp.int32))


def _bucket_buffer(counts, bm, rows):
    """The bucket buffer of `counts [E]` copies an expert, `rows` of them
    kept: (aoff [E+1], each bucket's start aligned to bm; M, its rows,
    STATIC; gbuf [M], each row's expert, E past the buckets)."""
    E = counts.shape[0]
    aoff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(_round_up(counts, bm))])   # [E+1]
    M = _round_up(rows, bm) + E * bm
    gbuf = jnp.searchsorted(aoff[1:], jnp.arange(M, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    return aoff, M, gbuf


def ragged_layout(gids_all, E, bm, rows=None):
    """Sort-based static-shape ragged bucket layout.

    gids_all: [Nk] int32 expert id per token copy, E = trash (unrouted).
    Returns (order, rank, dest, gbuf, counts):
      order  [Nk] — stable argsort by expert id (the permutation);
      rank   [Nk] — position of sorted copy j within its expert bucket;
      dest   [Nk] — destination row of sorted copy j in the bucket buffer
                    (bucket starts aligned to bm; trash after the buckets);
      gbuf   [M]  — per-buffer-row expert id: each expert's WHOLE aligned
                    region (alignment padding included — padded rows are
                    zero and never gathered back, so labeling them keeps
                    every block's id range a single expert and the kernel
                    skip exact) carries its id; E past the buckets.
                    M = round_up(Nk, bm) + E*bm STATIC;
      counts [E]  — tokens routed per expert (int32).
    `scatter(x[order]) -> gather(dest)` is the identity on payloads — the
    permutation round-trip the dispatch tests assert.

    `rows` bounds the buffer for a caller that holds a share of the experts
    (most copies are trash): order, rank and dest are cut to the first
    `rows` sorted copies (routed ones sort first) and M = round_up(rows,
    bm) + E*bm, so every kept copy's dest is inside the buffer. Routed
    copies past `rows` are left out: `sum(counts) - rows` of them, when
    that is positive, and the caller's to count."""
    (Nk,) = gids_all.shape
    rows = Nk if rows is None else min(rows, Nk)
    counts_full = jnp.zeros((E + 1,), jnp.int32).at[gids_all].add(1)
    counts = counts_full[:E]
    order = _order_by_group(gids_all, counts_full)[:rows]
    sorted_g = jnp.take(gids_all, order)
    raw_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts_full)[:-1]])   # [E+1]
    rank = jnp.arange(rows, dtype=jnp.int32) - jnp.take(raw_start, sorted_g)
    aoff, _, gbuf = _bucket_buffer(counts, bm, rows)
    dest = jnp.where(sorted_g < E,
                     jnp.take(aoff, jnp.minimum(sorted_g, E - 1)) + rank,
                     aoff[E] + rank)
    return order, rank, dest, gbuf, counts


def pair_rows(gids_all, E, bm, rows):
    """`ragged_layout(rows=)` told by copy and not by sorted position, for a
    caller that moves its rows by index arrays (`ops.pallas.moe_rows`):
    (pair_row [Nk], the buffer row of each copy, M for a copy that is trash
    or past the `rows` kept; gbuf [M]; counts [E]; the rows up to which the
    buckets reach, a multiple of bm). The same rows as `ragged_layout`'s
    `dest`; with few groups no sort, no permutation and no scatter."""
    (Nk,) = gids_all.shape
    rows = min(rows, Nk)
    place, counts_full = _place_by_group(gids_all, E + 1)
    start = jnp.cumsum(counts_full) - counts_full
    aoff, M, gbuf = _bucket_buffer(counts_full[:E], bm, rows)
    kept = (gids_all < E) & (place < rows)
    row = jnp.take(aoff, jnp.minimum(gids_all, E - 1)) + place - jnp.take(start, gids_all)
    return (jnp.where(kept, row, M).astype(jnp.int32), gbuf, counts_full[:E],
            jnp.minimum(aoff[E], M))


def _act(h, act):
    return jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)


def _expert_ffn_grouped(x, gids, w1, b1, w2, b2, act, block_rows, backend):
    """Two grouped matmuls + biases over ragged expert buckets. x [M, d],
    gids [M] in [0, G] (G = trash), weights this rank's expert shard
    [G, ...]. Returns fp32 [M, d]. Trash rows (gids == G) stay zero (the
    kernels never match them; the appended zero bias row is what they
    gather). In-bucket ALIGNMENT rows carry their bucket's id, so they
    come out as act(b1[g]) @ w2[g] + b2[g] — nonzero, but zero-payload
    and never gathered back by the dispatcher; don't reduce over ybuf
    without masking via dest."""
    g = w1.shape[0]
    # the dispatcher's layout: every row block is one group's
    h1 = grouped_matmul(x, w1, gids, block_rows=block_rows, backend=backend,
                        aligned=True)
    b1p = jnp.concatenate(
        [b1.reshape(g, -1), jnp.zeros((1, b1.shape[-1]), b1.dtype)])
    h1 = h1 + jnp.take(b1p, gids, axis=0).astype(jnp.float32)
    a = _act(h1, act).astype(x.dtype)
    y = grouped_matmul(a, w2, gids, block_rows=block_rows, backend=backend,
                       aligned=True)
    b2p = jnp.concatenate(
        [b2.reshape(g, -1), jnp.zeros((1, b2.shape[-1]), b2.dtype)])
    return y + jnp.take(b2p, gids, axis=0).astype(jnp.float32)


def _shared_ffn(xv, shared, act):
    """The dense shared-expert branch (replicated weights), or None."""
    if not shared:
        return None
    sw1, sb1, sw2, sb2 = shared
    h = _act(xv @ sw1 + sb1, act)
    return (h @ sw2 + sb2).astype(jnp.float32)


def _gshard_aux(probs, topi, E):
    """THE GShard load-balance aux loss (one implementation — the
    dropless==capacity parity contract depends on both dispatch modes
    computing it identically)."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=1),
                  axis=0)
    return jnp.sum(me * ce) * E


def _reduce_stats(l_aux, dropped, counts, token_axes, other_axes):
    """The shared stat-reduction convention of every dispatch body:
    dropped/counts sum over token shards, everything averages over the
    remaining mesh axes."""
    counts = counts.astype(jnp.float32)
    if token_axes:
        dropped = jax.lax.psum(dropped, token_axes)
        counts = jax.lax.psum(counts, token_axes)
        l_aux = jax.lax.pmean(l_aux, token_axes)
    if other_axes:
        dropped = jax.lax.pmean(dropped, other_axes)
        counts = jax.lax.pmean(counts, other_axes)
        l_aux = jax.lax.pmean(l_aux, other_axes)
    return l_aux, dropped, counts


def _dropless_moe(xv, gv, rng, w1, b1, w2, b2, *shared, E, k, act,
                  ep, ep_axis, token_axes, other_axes,
                  routing=(), rng_axes=None, block_rows=0, backend=None):
    """Token-choice dropless dispatch on LOCAL arrays (see module doc).

    xv [N, d] this rank's tokens, gv [N, E] gate logits; w/b are this
    rank's expert shard ([E//ep, ...] when ep > 1). `shared` optionally
    carries the replicated shared-expert MLP (sw1, sb1, sw2, sb2).
    Returns (out [N, d], l_aux, dropped=0, counts [E])."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import _route

    N, d = xv.shape
    rng = jax.random.wrap_key_data(rng)
    for ax in (token_axes if rng_axes is None else rng_axes):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
    topv, topi, probs = _route(gv.astype(jnp.float32), rng, k=k,
                               routing=routing)

    Nk = N * k
    bm = block_rows or pick_block_rows(Nk, E)
    flat_e = topi.reshape(-1)                                     # [Nk]
    routed = flat_e >= 0
    # -1 (GShard random-routing drop) -> the trash group E: those copies
    # ride the layout with combine weight 0 and are never computed
    gids_all = jnp.where(routed, flat_e, E).astype(jnp.int32)
    # sort-based ragged layout: stable argsort by expert id; rank within
    # bucket = sorted position minus the bucket's first sorted position
    order, rank, dest, gbuf, counts = ragged_layout(gids_all, E, bm)
    sorted_g = jnp.take(gids_all, order)
    tok_sorted = order // k                                       # [Nk]
    # copy j of token t sits at flat row t*k+j, so the sorted payload is
    # one gather of xv — no [Nk, d] repeat intermediate
    xs = jnp.take(xv, tok_sorted, axis=0)                         # [Nk, d]
    wgt_sorted = (jnp.take(topv.reshape(-1), order)
                  * jnp.take(routed, order).astype(jnp.float32))  # fp32

    if ep > 1:
        El = E // ep
        # destination owner + block-aligned slot within the owner's slice:
        # experts are contiguous per owner, so the sorted stream is too
        owner = sorted_g // El                                    # ep = trash
        le = sorted_g - owner * El
        counts2 = counts.reshape(ep, El)
        aligned2 = _round_up(counts2, bm)
        aoff2 = jnp.concatenate(
            [jnp.zeros((ep, 1), jnp.int32),
             jnp.cumsum(aligned2, axis=1)[:, :-1]], axis=1)       # [ep, El]
        cap = _round_up(Nk, bm) + El * bm                         # static
        slot = (aoff2[jnp.minimum(owner, ep - 1),
                      jnp.minimum(le, El - 1)] + rank)
        # trash rows (owner == ep) fall out of range -> dropped by scatter
        sbuf = jnp.zeros((ep, cap, d), xv.dtype).at[owner, slot].set(
            xs, mode="drop")
        # per-slice ids from the aligned offsets (padding rows carry their
        # bucket's id — zero payloads, single-expert blocks, exact skip)
        sgid = jax.vmap(lambda a: jnp.searchsorted(
            a, jnp.arange(cap, dtype=jnp.int32), side="right"))(
            jnp.cumsum(aligned2, axis=1)).astype(jnp.int32)
        # dispatch a2a (the reference global_scatter) — per-owner aligned
        # slices go to their expert owners
        rbuf = jax.lax.all_to_all(sbuf, ep_axis, 0, 0, tiled=True)
        rgid = jax.lax.all_to_all(sgid, ep_axis, 0, 0, tiled=True)
        # shared-expert branch HERE: no data dependence on either a2a, so
        # the scheduler overlaps it with the ICI transfers
        ysh = _shared_ffn(xv, shared, act)
        ybuf = _expert_ffn_grouped(rbuf.reshape(ep * cap, d),
                                   rgid.reshape(ep * cap),
                                   w1, b1, w2, b2, act, bm, backend)
        # combine a2a (the reference global_gather), back at the source
        yret = jax.lax.all_to_all(
            ybuf.astype(xv.dtype).reshape(ep, cap, d), ep_axis, 0, 0,
            tiled=True)
        yk = yret[jnp.minimum(owner, ep - 1), slot].astype(jnp.float32)
    else:
        M = gbuf.shape[0]
        buf = jnp.zeros((M, d), xv.dtype).at[dest].set(xs)
        ysh = _shared_ffn(xv, shared, act)
        ybuf = _expert_ffn_grouped(buf, gbuf, w1, b1, w2, b2, act, bm,
                                   backend)
        yk = jnp.take(ybuf, dest, axis=0)                         # fp32

    # unpermute + combine with the gate weights in fp32 (one scatter-add
    # over the token axis folds the k copies)
    out = jnp.zeros((N, d), jnp.float32).at[tok_sorted].add(
        yk * wgt_sorted[:, None])
    if ysh is not None:
        out = out + ysh
    l_aux, dropped, counts = _reduce_stats(
        _gshard_aux(probs, topi, E), jnp.zeros((), jnp.float32), counts,
        token_axes, other_axes)
    return out.astype(xv.dtype), l_aux.astype(xv.dtype), dropped, counts


def _expert_choice_moe(xv, gv, rng, w1, b1, w2, b2, *shared, E, k, act,
                       ep, ep_axis, token_axes, other_axes,
                       routing=(), rng_axes=None, block_rows=0,
                       backend=None):
    """Expert-choice routing (Zhou et al.): every expert picks its top-C
    tokens by router score, C = k*N/E rounded to the block size — buckets
    are all full, all equal, all block-aligned, so the layout is static by
    construction and nothing can overflow. Tokens may be picked by zero or
    several experts; combine weights are the picked softmax scores (fp32).
    Load is perfectly balanced, so l_aux = 0."""
    N, d = xv.shape
    probs = jax.nn.softmax(gv.astype(jnp.float32), axis=-1)       # [N, E]
    import math

    C0 = max(1, (k * N + E - 1) // E)
    bm = block_rows or pick_block_rows(E * _round_up(C0, 8), E)
    bm = min(bm, max(8, N))
    C = min(_round_up(C0, bm), (N // bm) * bm) or N
    if C % bm:
        bm = math.gcd(bm, C)
    ev, ei = jax.lax.top_k(jnp.transpose(probs), C)               # [E, C]
    flat_i = ei.reshape(-1)                                       # [E*C]
    bufx = jnp.take(xv, flat_i, axis=0)                           # [E*C, d]

    if ep > 1:
        El = E // ep
        # expert-major layout: owner slices are static [El*C, d] blocks
        sbuf = bufx.reshape(ep, El * C, d)
        rbuf = jax.lax.all_to_all(sbuf, ep_axis, 0, 0, tiled=True)
        ysh = _shared_ffn(xv, shared, act)
        gids = jnp.tile(jnp.repeat(jnp.arange(El, dtype=jnp.int32), C), ep)
        ybuf = _expert_ffn_grouped(rbuf.reshape(ep * El * C, d), gids,
                                   w1, b1, w2, b2, act, bm, backend)
        yret = jax.lax.all_to_all(
            ybuf.astype(xv.dtype).reshape(ep, El * C, d), ep_axis, 0, 0,
            tiled=True)
        y = yret.reshape(E * C, d).astype(jnp.float32)
    else:
        gids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), C)
        ysh = _shared_ffn(xv, shared, act)
        y = _expert_ffn_grouped(bufx, gids, w1, b1, w2, b2, act, bm, backend)

    out = jnp.zeros((N, d), jnp.float32).at[flat_i].add(
        y * ev.reshape(-1)[:, None])
    if ysh is not None:
        out = out + ysh

    l_aux, dropped, counts = _reduce_stats(
        jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
        jnp.full((E,), float(C), jnp.float32), token_axes, other_axes)
    return (out.astype(xv.dtype), l_aux.astype(xv.dtype), dropped, counts)
