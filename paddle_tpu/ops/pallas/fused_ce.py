"""Fused chunked LM-head + softmax-cross-entropy (never materialize logits).

Reference analog: the fused CE kernels production LLM stacks keep next to the
head projection — Liger-kernel's fused_linear_cross_entropy and Megatron's
vocab-parallel cross entropy (reference ParallelCrossEntropy,
fleet/layers/mpu/mp_layers.py:742). At LM scale the `[tokens, vocab]` logits
tensor is the single largest HBM spike of a train step (LLaMA-2-7B at
batch*seq=4096, vocab 32000: 512 MB in fp32), and it is pure overhead — the
loss needs only three per-token scalars (max, log-sum-exp, target logit).

TPU-native design: a `jax.custom_vjp` computes
``loss = CE(x @ W + b, labels)`` in chunks so the full logits never exist in
forward OR backward.

Passes over the head. A REDUCED loss (`reduction` "mean", "sum" or
"mean_all": every train step's) takes ONE pass when differentiated
(`_one_pass`): a scan over token chunks projects each chunk's `[C, V]` fp32
logits tile once, reduces it to the softmax statistics, forms the loss and
`d = dloss/dlogits` with the reduction's per-token weights (known before
the call) where a per-token cotangent would go, and runs `dx = d W^T` and
`dW += x^T d`: three head products a step, and the backward only scales the
kept dx / dW / db by the scalar cotangent (at 1 or a power of two the
gradients are bit-equal to the two-pass path's). Evaluated without a
gradient, the same entry runs the statistics forward alone. Two passes
remain — a statistics forward and a backward that projects every tile
again, four products — for per-token losses (`reduction="none"`), the
logits-level loss and the `vocab` variant. The statistics forward's
variants:

* **token-chunked** (`variant="tokens"`): `lax.scan` over token chunks; each
  chunk materializes only a `[C, V]` logits tile in fp32, reduces it to the
  per-token stats, and is freed before the next chunk. Its backward scans
  token chunks too, recomputing the tile and accumulating `dW`/`db` in fp32,
  at a depth of its own (below).
* **vocab-chunked** (`variant="vocab"`): `lax.scan` over vocab chunks with
  online (flash-style) max/sum-exp rescaling — the right shape when the
  token count is small but the vocabulary is huge; its backward scans the
  vocabulary, so a reduced loss keeps two passes here.
* **pallas** (`variant="pallas"`, `ce_stats`): a Pallas kernel grids over
  (token-block, vocab-block) and keeps the running max/sum-exp/target/sum
  accumulators resident in VMEM, one MXU matmul per tile; on a CPU backend
  it runs in interpreter mode (fake-device pattern, SURVEY §4.4) so tier-1
  CPU tests exercise the identical kernel body. Backward reuses the chunked
  scan (already logits-free).

* **mp-parallel softmax**: when the "mp" mesh axis is bound (shard_map — the
  pipelined runtimes and manual-collective TP), each rank keeps only its
  vocab shard: labels shift into the local range, the per-token stats reduce
  with `pmax`/`psum` over the axis (Megatron fwd; inside the one pass, per
  chunk), and the partial `dx` is `psum`med while `dW` stays shard-local
  (Megatron bwd) — no rank ever holds a full vocab row. Under a GSPMD mesh
  the entry runs Mosaic and the one pass per shard in its own shard_map.

Blocking of the loop that makes the products: with a head, every iteration
reads and writes the whole fp32 `dW` accumulator and streams the head twice,
whatever the chunk, so it does not inherit the forward's 4M-element tile
(128 tokens at vocab 32768: 96 such passes a 12K-token step). It takes
`resolve_bwd_chunk`'s depth instead (2048 tokens, byte-capped), unless a
`chunk_tokens` the caller or `FLAGS_fused_ce_chunk_tokens` SET binds it as a
memory bound; a tuned or heuristic forward tile does not. The logits-level
loss (no head, no accumulator) keeps the forward's chunk. The depth is no
knob: `last_resolution("fused_ce").derived` shows it, and `passes` (1 or 2)
says which path a call takes when differentiated.

Numerics: per-chunk logits, all stats and all gradient accumulators are
fp32 regardless of input dtype (bf16-safe); label smoothing, ignore_index
and a z-loss hook (`z_loss * logsumexp^2`, the PaLM/Megatron stabilizer)
are folded into the same chunked pass so they never force the unfused path.

Exports raw-array functions; the Tensor-level surface lives in
`paddle_tpu.nn.functional` (`cross_entropy` fast path,
`parallel_cross_entropy`, `fused_linear_cross_entropy`) and
`paddle_tpu.incubate.nn.FusedLinearCrossEntropy`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas._compat import x64_off

__all__ = ["fused_linear_cross_entropy_loss", "softmax_cross_entropy_loss",
           "resolve_chunks", "resolve_bwd_chunk", "x64_off"]

_NEG_INF = float(np.finfo(np.float32).min)


def _mp_info(mp_axis):
    """(axis_name, world) when `mp_axis` names a bound shard_map axis."""
    if not mp_axis:
        return None, 1
    from paddle_tpu.distributed.collective import _bound_axes

    if not _bound_axes((mp_axis,)):
        return None, 1
    return mp_axis, jax.lax.psum(1, mp_axis)


class _CECfg(NamedTuple):
    """Static (hashable) config keying one compiled custom_vjp instance."""
    ignore_index: int
    label_smoothing: float
    z_loss: float
    chunk_tokens: int
    chunk_vocab: int
    variant: str          # "tokens" | "vocab" | "pallas"
    mp_axis: str | None   # bound shard_map axis name, or None
    has_w: bool
    has_bias: bool
    # token depth of `_bwd_tokens` (resolve_bwd_chunk, or the caller's /
    # flag's chunk_tokens when one was set; variant "vocab" scans the
    # vocabulary in its backward and does not read it)
    bwd_chunk_tokens: int
    # fp8_policy='matmuls+head': the head projection (and the backward
    # dx/dW matmuls, with the d-logits tile in e5m2) run through float8 with
    # current scaling; per-token softmax stats and accumulators stay fp32
    fp8: bool = False


def _check_labels(labels):
    """The unfused gather rejected float labels at trace time; keep that
    contract — astype(int32) would silently truncate them instead."""
    if not jnp.issubdtype(jnp.asarray(labels).dtype, jnp.integer):
        raise TypeError(
            "fused cross-entropy takes integer class labels, got dtype "
            f"{jnp.asarray(labels).dtype}; for probabilistic targets use "
            "soft_label=True (the unfused path)")


def resolve_chunks(n_tokens: int, vocab: int, chunk_tokens: int = 0,
                   chunk_vocab: int = 0) -> tuple[int, int]:
    """Default chunk sizes bounding the live logits tile to ~4M fp32 elements
    (16 MB — comfortably inside VMEM-adjacent working set on TPU, cheap on
    CPU). Flag/arg overrides win when positive."""
    target = 1 << 22
    ct = chunk_tokens if chunk_tokens > 0 else max(
        16, min(n_tokens, target // max(vocab, 1)))
    cv = chunk_vocab if chunk_vocab > 0 else max(
        128, min(vocab, target // max(n_tokens, 1)))
    return min(ct, max(n_tokens, 1)), min(cv, max(vocab, 1))


# The head's backward carries the fp32 dW accumulator [hidden, vocab_local]
# through its scan and reads and writes all of it (8 bytes an element) every
# iteration to add a product that is only `chunk` deep: 2*chunk operations
# for 8 bytes, chunk/4 an HBM byte, whatever the hidden size. A v5e's ridge
# (197 TFLOP/s over 819 GB/s = 240) is met at 960 tokens; 2048 pays for the
# pass twice over. The fp32 logits tile is the only memory that grows with
# the depth, so it is what the depth is capped by.
_BWD_DEPTH = 2048
_BWD_TILE_BYTES = 256 << 20


def resolve_bwd_chunk(n_tokens: int, vocab_local: int) -> int:
    """Token depth of the head's backward scan (`_bwd_tokens` with a head):
    `_BWD_DEPTH`, less where the fp32 `[chunk, vocab_local]` logits tile
    would pass `_BWD_TILE_BYTES`, a multiple of 128, at most all tokens.
    Tokens are spread evenly over the iterations, so a ragged tail pads by
    under 128 rows an iteration instead of up to a whole chunk. Reads the
    two shapes only: no flag, no device query."""
    n = max(int(n_tokens), 1)
    cap = _BWD_TILE_BYTES // (4 * max(int(vocab_local), 1))
    depth = max(128, min(_BWD_DEPTH, cap) // 128 * 128)
    if n <= depth:
        return n
    iters = -(-n // depth)
    per_iter = -(-n // iters)
    return -(-per_iter // 128) * 128


# ---------------------------------------------------------------------------
# per-token stats: m (running max), s (sum exp shifted), t (target logit),
# sl (sum of logits — label-smoothing mean term). All fp32, shape [N].
# ---------------------------------------------------------------------------


def _chunk_stats(logits, labels_c):
    """Stats of one fp32 logits tile [C, V_local] against local labels [C]."""
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    m = jnp.max(logits, axis=-1)
    s = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    hit = col == labels_c[:, None]
    t = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    sl = jnp.sum(logits, axis=-1)
    return m, s, t, sl


def _fp8_mm(a, b, a_e5m2=False):
    """Current-scaled fp8 matmul, fp32 out (no vjp of its own — the fused-CE
    custom_vjp owns forward AND backward, so forward tiles, the backward's
    recomputed tiles, and the dx/dW products all quantize consistently)."""
    from paddle_tpu.amp.fp8 import fp8_matmul

    return fp8_matmul(a, b,
                      a_dtype=jnp.float8_e5m2 if a_e5m2 else None)


def _project(x_c, w, b, fp8=False):
    if fp8:
        out = _fp8_mm(x_c, w)
    else:
        out = jnp.dot(x_c.astype(jnp.float32), w.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out


def _pad_tokens(x, labels, chunk):
    n = x.shape[0]
    nc = -(-n // chunk)
    pad = nc * chunk - n
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    return x, labels, nc


def _stats_tokens(cfg: _CECfg, x, w, b, labels_loc):
    """Token-chunked scan. With has_w, x is [N, H] and each chunk projects
    to a [C, V] fp32 tile; without, x IS the logits and chunks are slices."""
    n = x.shape[0]
    xp, lp, nc = _pad_tokens(x, labels_loc, cfg.chunk_tokens)
    xc = xp.reshape((nc, cfg.chunk_tokens) + xp.shape[1:])
    lc = lp.reshape(nc, cfg.chunk_tokens)

    def step(_, args):
        xi, li = args
        logits = (_project(xi, w, b, cfg.fp8) if cfg.has_w
                  else xi.astype(jnp.float32))
        return None, _chunk_stats(logits, li)

    _, (m, s, t, sl) = jax.lax.scan(step, None, (xc, lc))
    return tuple(a.reshape(-1)[:n] for a in (m, s, t, sl))


def _pad_vocab(w, b, vloc, chunk):
    nc = -(-vloc // chunk)
    pad = nc * chunk - vloc
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        if b is not None:
            b = jnp.pad(b, (0, pad))
    return w, b, nc


def _stats_vocab(cfg: _CECfg, x, w, b, labels_loc):
    """Vocab-chunked scan with online max/sum-exp rescaling (flash-softmax
    recurrence) — [N, CV] tiles, never [N, V]."""
    n, vloc = x.shape[0], w.shape[1]
    cv = cfg.chunk_vocab
    wp, bp, nc = _pad_vocab(w, b, vloc, cv)
    wc = jnp.moveaxis(wp.reshape(wp.shape[0], nc, cv), 1, 0)  # [nc, H, cv]
    bc = (bp.reshape(nc, cv) if b is not None else None)
    xf = x.astype(jnp.float32)

    def step(carry, args):
        m, s, t, sl = carry
        j = args[0]
        wi = args[1]
        if cfg.fp8:
            logits = _fp8_mm(xf, wi)
        else:
            logits = jnp.dot(xf, wi.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        if bc is not None:
            logits = logits + args[2].astype(jnp.float32)
        col = j * cv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        in_v = col < vloc
        bm = jnp.max(jnp.where(in_v, logits, _NEG_INF), axis=-1)
        nm = jnp.maximum(m, bm)
        s = s * jnp.exp(m - nm) + jnp.sum(
            jnp.where(in_v, jnp.exp(logits - nm[:, None]), 0.0), axis=-1)
        t = t + jnp.sum(jnp.where(col == labels_loc[:, None], logits, 0.0),
                        axis=-1)
        sl = sl + jnp.sum(jnp.where(in_v, logits, 0.0), axis=-1)
        return (nm, s, t, sl), None

    init = (jnp.full((n,), _NEG_INF, jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    xs = (jnp.arange(nc, dtype=jnp.int32), wc) + ((bc,) if bc is not None else ())
    (m, s, t, sl), _ = jax.lax.scan(step, init, xs)
    return m, s, t, sl


# ---------------------------------------------------------------------------
# Pallas stats kernel: grid (token blocks, vocab blocks); running accumulators
# live in the revisited output blocks (the sequential-grid idiom the rmsnorm
# kernel's dw accumulation uses). Stats are broadcast over a 128-lane row to
# satisfy tiling; column 0 is read back.
# ---------------------------------------------------------------------------


def _ce_stats_kernel(x_ref, w_ref, lab_ref, m_ref, s_ref, t_ref, sl_ref,
                     *, bv: int, vloc: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        sl_ref[...] = jnp.zeros_like(sl_ref)

    logits = jnp.dot(x_ref[...].astype(jnp.float32),
                     w_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    in_v = col < vloc
    lab = lab_ref[:, :1]  # labels lane-replicated; column 0 is the value
    m_prev = m_ref[:, :1]
    bm = jnp.max(jnp.where(in_v, logits, _NEG_INF), axis=-1, keepdims=True)
    nm = jnp.maximum(m_prev, bm)
    s = s_ref[:, :1] * jnp.exp(m_prev - nm) + jnp.sum(
        jnp.where(in_v, jnp.exp(logits - nm), 0.0), axis=-1, keepdims=True)
    t = t_ref[:, :1] + jnp.sum(jnp.where(col == lab, logits, 0.0),
                               axis=-1, keepdims=True)
    sl = sl_ref[:, :1] + jnp.sum(jnp.where(in_v, logits, 0.0),
                                 axis=-1, keepdims=True)
    lanes = m_ref.shape[-1]
    m_ref[...] = jnp.broadcast_to(nm, (nm.shape[0], lanes))
    s_ref[...] = jnp.broadcast_to(s, (s.shape[0], lanes))
    t_ref[...] = jnp.broadcast_to(t, (t.shape[0], lanes))
    sl_ref[...] = jnp.broadcast_to(sl, (sl.shape[0], lanes))


def _stats_pallas(cfg: _CECfg, x, w, labels_loc, interpret=None):
    n, h = x.shape
    vloc = w.shape[1]
    # Mosaic tiles blocks in (16, 128) bf16 / (8, 128) fp32 units: the chunk
    # heuristic bounds the scan variants' logits tile and is free to be odd
    # (131 tokens at vocab 32000), so the kernel's own tile rounds down to
    # the hardware unit (the padding below absorbs a short tail)
    br = max(16, min(cfg.chunk_tokens, 256, n) // 16 * 16)
    bv = max(128, min(cfg.chunk_vocab, 512, vloc) // 128 * 128)
    xp, lp, ni = _pad_tokens(x, labels_loc, br)
    wp, _, nj = _pad_vocab(w, None, vloc, bv)
    if interpret is None:
        interpret = not _compat.on_tpu()
    kern = functools.partial(_ce_stats_kernel, bv=bv, vloc=vloc)
    stat = jax.ShapeDtypeStruct((ni * br, 128), jnp.float32)
    # labels lane-replicated to a (rows, 128) int32 tile (min int tiling)
    lab = jnp.broadcast_to(lp.astype(jnp.int32)[:, None], (ni * br, 128))
    with x64_off():
        m, s, t, sl = pl.pallas_call(
            kern,
            grid=(ni, nj),
            in_specs=[pl.BlockSpec((br, h), lambda i, j: (i, 0)),
                      pl.BlockSpec((h, bv), lambda i, j: (0, j)),
                      pl.BlockSpec((br, 128), lambda i, j: (i, 0))],
            out_specs=[pl.BlockSpec((br, 128), lambda i, j: (i, 0))] * 4,
            out_shape=[stat] * 4,
            interpret=interpret,
            **_compat.kernel_name("ce_stats"),
        )(xp, wp, lab)
    return tuple(a[:n, 0] for a in (m, s, t, sl))


# ---------------------------------------------------------------------------
# forward assembly + backward (shared by all variants)
# ---------------------------------------------------------------------------


def _local_labels(cfg: _CECfg, labels, vloc):
    """Shift labels into the local vocab shard range under bound mp; out-of-
    shard (and ignore_index) labels fall outside [0, vloc) and match nothing."""
    axis, world = _mp_info(cfg.mp_axis)
    if axis is None:
        return labels.astype(jnp.int32), None, vloc
    off = jax.lax.axis_index(axis).astype(jnp.int32) * vloc
    return labels.astype(jnp.int32) - off, axis, vloc * world


def _merge_stats(axis, m, s, t, sl):
    """Per-token lse, target logit and logit sum from one shard's stats:
    under bound mp the Megatron reduction of the [tokens] vectors over the
    axis (no rank holds a full vocabulary row)."""
    lse = m + jnp.log(s)
    if axis is not None:
        g = jax.lax.pmax(lse, axis)
        lse = g + jnp.log(jax.lax.psum(jnp.exp(lse - g), axis))
        t = jax.lax.psum(t, axis)
        sl = jax.lax.psum(sl, axis)
    return lse, t, sl


def _nll(cfg: _CECfg, lse, t, sl, v_total):
    eps = cfg.label_smoothing
    nll = lse - t if eps == 0.0 else lse - (1.0 - eps) * t - eps * sl / v_total
    if cfg.z_loss:
        nll = nll + cfg.z_loss * lse * lse
    return nll


def _fwd_impl(cfg: _CECfg, x, w, b, labels):
    vloc = w.shape[1] if cfg.has_w else x.shape[-1]
    lab_loc, axis, v_total = _local_labels(cfg, labels, vloc)
    if cfg.variant == "vocab" and cfg.has_w:
        m, s, t, sl = _stats_vocab(cfg, x, w, b, lab_loc)
    elif cfg.variant == "pallas" and cfg.has_w and b is None:
        m, s, t, sl = _stats_pallas(cfg, x, w, lab_loc)
    else:
        m, s, t, sl = _stats_tokens(cfg, x, w, b, lab_loc)
    lse, t, sl = _merge_stats(axis, m, s, t, sl)
    valid = labels != cfg.ignore_index
    return jnp.where(valid, _nll(cfg, lse, t, sl, v_total), 0.0), lse


def _ct_valid(cfg: _CECfg, labels, ct):
    """The per-token cotangent in fp32, 0 at ignored tokens."""
    return jnp.where(labels != cfg.ignore_index, ct.astype(jnp.float32), 0.0)


def _chunk_dlogits(cfg: _CECfg, logits, lab_c, lse_c, ctv_c, v_total):
    """d loss / d logits for one fp32 tile: p*coef - (1-eps)*ct*onehot -
    (eps/V)*ct with coef = ct (1 + 2 z lse) — the chunked form of
    softmax-minus-onehot."""
    eps = cfg.label_smoothing
    p = jnp.exp(logits - lse_c[:, None])
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    coef_c = (ctv_c * (1.0 + 2.0 * cfg.z_loss * lse_c) if cfg.z_loss
              else ctv_c)
    d = p * coef_c[:, None]
    d = d - jnp.where(col == lab_c[:, None],
                      (1.0 - eps) * ctv_c[:, None], 0.0)
    if eps:
        d = d - (eps / v_total) * ctv_c[:, None]
    return d


def _mp_fix_grads(cfg: _CECfg, axis, dx, dw, db):
    """Cotangent bookkeeping under bound mp (shard_map with replication
    checking off, the repo-wide shard_map_compat convention): the cotangent
    of the replicated per-token loss arrives pre-divided by the axis size,
    and the boundary transpose psums only REPLICATED inputs. So:
      * has_w: x is replicated — psum the partial dx (÷world × boundary psum
        nets out to the true total); w (and bias) are vocab-shard inputs whose
        cotangents pass through untouched — scale them back by world.
      * logits-level (no w): the logits input is itself vocab-sharded — its
        local d-logits tile is already complete, only the ÷world undone.
    The one pass applies the same fix in its forward, to gradients taken at
    the per-token weights: the reduced scalar is replicated over mp like the
    per-token loss, so its cotangent arrives ÷world the same way (a psum
    over the token axes inside the entry's own shard_map transposes to the
    ÷ of those axes it undoes), and scaling by it commutes with the fix.
    Parity-gated by the mp cases of tests/test_fused_cross_entropy.py."""
    if axis is None:
        return dx, dw, db
    world = jax.lax.psum(1, axis)
    if not cfg.has_w:
        return dx * world, dw, db
    dx = jax.lax.psum(dx, axis) if dx is not None else None
    dw = dw * world
    if db is not None:
        db = db * world
    return dx, dw, db


def _head_grads(cfg: _CECfg, carry, xi, d, wf):
    """One token chunk's share of the head's gradients from its d-logits
    tile: dx_chunk = d W^T, and x_chunk^T d (and d's column sums) added to
    the fp32 accumulators."""
    dw_acc, db_acc = carry
    if cfg.fp8:
        # gradient tile in e5m2, x/w in e4m3; the dw accumulator stays
        # fp32 (only the matmuls change precision)
        dxi = _fp8_mm(d, wf.T, a_e5m2=True)
        dw_acc = dw_acc + _fp8_mm(d.T, xi.astype(jnp.float32),
                                  a_e5m2=True).T
    else:
        dxi = jnp.dot(d, wf.T, preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jnp.dot(xi.astype(jnp.float32).T, d,
                                  preferred_element_type=jnp.float32)
    if db_acc is not None:
        db_acc = db_acc + jnp.sum(d, axis=0)
    return (dw_acc, db_acc), dxi


def _grad_init(cfg: _CECfg, w):
    return (jnp.zeros(w.shape, jnp.float32),
            jnp.zeros((w.shape[1],), jnp.float32) if cfg.has_bias else None)


def _bwd_tokens(cfg: _CECfg, x, w, b, labels, lse, ct):
    n = x.shape[0]
    vloc = w.shape[1] if cfg.has_w else x.shape[-1]
    lab_loc, axis, v_total = _local_labels(cfg, labels, vloc)
    ctv = _ct_valid(cfg, labels, ct)
    c = cfg.bwd_chunk_tokens
    xp, lp, nc = _pad_tokens(x, lab_loc, c)
    aux = jnp.stack([jnp.pad(lse, (0, nc * c - n)),
                     jnp.pad(ctv, (0, nc * c - n))], axis=-1)
    xc = xp.reshape((nc, c) + xp.shape[1:])
    lc = lp.reshape(nc, c)
    ac = aux.reshape(nc, c, 2)
    wf = w.astype(jnp.float32) if cfg.has_w else None

    def step(carry, args):
        xi, li, ai = args
        logits = (_project(xi, w, b, cfg.fp8) if cfg.has_w
                  else xi.astype(jnp.float32))
        d = _chunk_dlogits(cfg, logits, li, ai[:, 0], ai[:, 1], v_total)
        if not cfg.has_w:
            return carry, d
        return _head_grads(cfg, carry, xi, d, wf)

    init = _grad_init(cfg, w) if cfg.has_w else None
    carry, dxs = jax.lax.scan(step, init, (xc, lc, ac))
    dx = dxs.reshape((nc * c,) + dxs.shape[2:])[:n]
    dx, dw_acc, db_acc = _mp_fix_grads(
        cfg, axis, dx, *(carry if cfg.has_w else (None, None)))
    dx = dx.astype(x.dtype)
    if not cfg.has_w:
        return dx, None, None
    return dx, dw_acc.astype(w.dtype), (
        db_acc.astype(b.dtype) if cfg.has_bias else None)


def _one_pass(cfg: _CECfg, x, w, b, labels, tw):
    """The reduced loss `sum_i tw_i * nll_i` AND its gradients at a
    cotangent of 1, in ONE scan over token chunks at the backward's depth:
    each chunk's fp32 logits tile is projected once, reduced to the
    softmax statistics (over the mp axis when it is bound), and turned into
    its d-logits tile with `tw` where the two-pass backward puts the
    per-token cotangent; then the same two products as `_bwd_tokens`.
    Three head products where the two passes make four."""
    n, vloc = x.shape[0], w.shape[1]
    lab_loc, axis, v_total = _local_labels(cfg, labels, vloc)
    c = cfg.bwd_chunk_tokens
    xp, lp, nc = _pad_tokens(x, lab_loc, c)
    xc = xp.reshape((nc, c) + xp.shape[1:])
    lc = lp.reshape(nc, c)
    # tw is 0 at ignored tokens (`_token_weights`) and at the padding
    cc = jnp.pad(tw, (0, nc * c - n)).reshape(nc, c)
    wf = w.astype(jnp.float32)

    def step(carry, args):
        xi, li, ci = args
        logits = _project(xi, w, b, cfg.fp8)
        lse, t, sl = _merge_stats(axis, *_chunk_stats(logits, li))
        # the statistics need whole rows, so the tile goes through HBM and
        # d cannot be the projection's epilogue as in `_bwd_tokens`; the
        # barrier forms d once, where XLA would form it inside each product
        # again for every block of the product's output
        d = jax.lax.optimization_barrier(
            _chunk_dlogits(cfg, logits, li, lse, ci, v_total))
        carry, dxi = _head_grads(cfg, carry, xi, d, wf)
        if axis is not None:
            # Megatron's sum of the partial dx over the vocabulary shards, a
            # chunk at a time: beside the next chunk's products, and what
            # the scan keeps is in the input's dtype
            dxi = jax.lax.psum(dxi, axis)
        return carry, (dxi.astype(x.dtype), _nll(cfg, lse, t, sl, v_total))

    carry, (dxs, nll) = jax.lax.scan(step, _grad_init(cfg, w), (xc, lc, cc))
    loss = jnp.sum(tw * nll.reshape(-1)[:n])
    _, dw, db = _mp_fix_grads(cfg, axis, None, *carry)
    return loss, (dxs.reshape((-1,) + dxs.shape[2:])[:n], dw.astype(w.dtype),
                  db.astype(b.dtype) if cfg.has_bias else None)


def _bwd_vocab(cfg: _CECfg, x, w, b, labels, lse, ct):
    n, vloc = x.shape[0], w.shape[1]
    lab_loc, axis, v_total = _local_labels(cfg, labels, vloc)
    ctv = _ct_valid(cfg, labels, ct)
    cv = cfg.chunk_vocab
    wp, bp, nc = _pad_vocab(w, b, vloc, cv)
    wc = jnp.moveaxis(wp.reshape(wp.shape[0], nc, cv), 1, 0)
    bc = bp.reshape(nc, cv) if b is not None else None
    xf = x.astype(jnp.float32)

    def step(dx_acc, args):
        j, wi = args[0], args[1]
        if cfg.fp8:
            logits = _fp8_mm(xf, wi)
        else:
            logits = jnp.dot(xf, wi.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        if bc is not None:
            logits = logits + args[2].astype(jnp.float32)
        # labels shifted into this chunk's [0, cv) frame, then padding
        # columns (>= vloc) zeroed
        d = _chunk_dlogits(cfg, logits, lab_loc - j * cv, lse, ctv, v_total)
        col = j * cv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        d = jnp.where(col < vloc, d, 0.0)
        if cfg.fp8:
            dx_acc = dx_acc + _fp8_mm(d, wi.astype(jnp.float32).T,
                                      a_e5m2=True)
            dwi = _fp8_mm(d.T, xf, a_e5m2=True)  # [cv, H]
        else:
            dx_acc = dx_acc + jnp.dot(d, wi.astype(jnp.float32).T,
                                      preferred_element_type=jnp.float32)
            dwi = jnp.dot(d.T, xf, preferred_element_type=jnp.float32)
        return dx_acc, (dwi, jnp.sum(d, axis=0))

    xs = (jnp.arange(nc, dtype=jnp.int32), wc) + ((bc,) if bc is not None else ())
    dx, (dwis, dbis) = jax.lax.scan(step, jnp.zeros(x.shape, jnp.float32), xs)
    dw = jnp.transpose(dwis, (2, 0, 1)).reshape(w.shape[0], nc * cv)[:, :vloc]
    db = dbis.reshape(nc * cv)[:vloc] if cfg.has_bias else None
    dx, dw, db = _mp_fix_grads(cfg, axis, dx, dw, db)
    return dx.astype(x.dtype), dw.astype(w.dtype), (
        db.astype(b.dtype) if cfg.has_bias else None)


# ---------------------------------------------------------------------------
# custom_vjp assembly (cached per static config)
# ---------------------------------------------------------------------------


def _label_zero(labels):
    return np.zeros(labels.shape, jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _build_linear_ce(cfg: _CECfg):
    if cfg.has_bias:
        @jax.custom_vjp
        def f(x, w, b, labels):
            return _fwd_impl(cfg, x, w, b, labels)[0]

        def fwd(x, w, b, labels):
            loss, lse = _fwd_impl(cfg, x, w, b, labels)
            return loss, (x, w, b, labels, lse)

        def bwd(res, ct):
            x, w, b, labels, lse = res
            bwd_fn = _bwd_vocab if cfg.variant == "vocab" else _bwd_tokens
            dx, dw, db = bwd_fn(cfg, x, w, b, labels, lse, ct)
            return dx, dw, db, _label_zero(labels)
    else:
        @jax.custom_vjp
        def f(x, w, labels):
            return _fwd_impl(cfg, x, w, None, labels)[0]

        def fwd(x, w, labels):
            loss, lse = _fwd_impl(cfg, x, w, None, labels)
            return loss, (x, w, labels, lse)

        def bwd(res, ct):
            x, w, labels, lse = res
            bwd_fn = _bwd_vocab if cfg.variant == "vocab" else _bwd_tokens
            dx, dw, _ = bwd_fn(cfg, x, w, None, labels, lse, ct)
            return dx, dw, _label_zero(labels)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _build_linear_ce_reduced(cfg: _CECfg):
    """`sum_i tw_i * nll_i` as one custom_vjp. The primal is the statistics
    forward alone (an evaluation pays no gradient products); under
    differentiation the forward rule is `_one_pass`, which leaves dx, dW
    (and db) at a cotangent of 1 in their own dtypes, and the backward rule
    only scales them by the scalar cotangent. The per-token weights `tw`
    are known before the call and take no gradient."""
    @jax.custom_vjp
    def f(x, w, b, labels, tw):
        return jnp.sum(tw * _fwd_impl(cfg, x, w, b, labels)[0])

    def fwd(x, w, b, labels, tw):
        loss, grads = _one_pass(cfg, x, w, b, labels, tw)
        return loss, (grads, labels, tw)

    def bwd(res, ct):
        grads, labels, tw = res
        dx, dw, db = (None if g is None else
                      (g.astype(jnp.float32) * ct).astype(g.dtype)
                      for g in grads)
        return dx, dw, db, _label_zero(labels), jnp.zeros_like(tw)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _build_softmax_ce(cfg: _CECfg):
    @jax.custom_vjp
    def f(logits, labels):
        return _fwd_impl(cfg, logits, None, None, labels)[0]

    def fwd(logits, labels):
        loss, lse = _fwd_impl(cfg, logits, None, None, labels)
        return loss, (logits, labels, lse)

    def bwd(res, ct):
        logits, labels, lse = res
        dx, _, _ = _bwd_tokens(cfg, logits, None, None, labels, lse, ct)
        return dx, _label_zero(labels)

    f.defvjp(fwd, bwd)
    return f


def _resolve_cfg(n, vloc, ignore_index, label_smoothing, z_loss, chunk_tokens,
                 chunk_vocab, variant, mp_axis, has_w, has_bias,
                 reduction="none"):
    from paddle_tpu.core.flags import flag
    from paddle_tpu.tuning.blocks import (Resolution, note_derived,
                                          resolve_blocks)

    if chunk_tokens > 0 or chunk_vocab > 0:
        # caller-supplied chunking wins outright (resolve_chunks fills a
        # partially-specified pair from the heuristic)
        ct, cv = resolve_chunks(n, vloc, chunk_tokens, chunk_vocab)
        res = Resolution("fused_ce", {"chunk_tokens": ct, "chunk_vocab": cv},
                         "caller", "chunk_tokens / chunk_vocab arguments")
    else:
        res = resolve_blocks(
            "fused_ce", {"n_tokens": int(n), "vocab": int(vloc)},
            default=lambda g: resolve_chunks(n, vloc))
        ct = min(int(res.values["chunk_tokens"]), max(int(n), 1))
        cv = min(int(res.values["chunk_vocab"]), max(int(vloc), 1))
    # fp8_policy='matmuls+head': the projection matmuls quantize (stats stay
    # fp32). The Pallas stats kernel is bf16/fp32-only, so fp8 resolves to
    # the token-chunked scan variant instead.
    from paddle_tpu.amp.fp8 import head_fp8_enabled

    fp8 = bool(has_w and head_fp8_enabled())
    if variant in (None, "", "auto"):
        variant = flag("fused_ce_variant")
    if variant in (None, "", "auto"):
        variant = ("pallas" if (has_w and not has_bias and _compat.on_tpu()
                                and not fp8)
                   else "tokens")
    if fp8 and variant == "pallas":
        variant = "tokens"
    # the backward's own depth. A chunk_tokens the caller or the flag set is
    # a memory bound someone asked for and binds the backward too; a tuned
    # or heuristic forward tile does not (it was sized for, and timed on,
    # the forward alone). Without a head there is no accumulator to amortise
    # and the backward keeps the forward's chunk.
    if chunk_tokens > 0:
        bwd, bwd_from = ct, "caller"
    elif res.provenance == "flag" and flag("fused_ce_chunk_tokens") > 0:
        bwd, bwd_from = ct, "flag"
    elif has_w:
        bwd, bwd_from = resolve_bwd_chunk(n, vloc), "shape"
    else:
        bwd, bwd_from = ct, "forward"
    # a reduced loss through a head takes ONE pass (`_one_pass`); per-token
    # losses, the logits-level loss and the vocab variant keep the
    # statistics forward and a backward of their own
    passes = 1 if (has_w and reduction != "none" and variant != "vocab") else 2
    note_derived(res, bwd_chunk_tokens=bwd, bwd_chunk_from=bwd_from,
                 passes=passes)
    if mp_axis == "auto":
        from paddle_tpu.distributed.collective import _bound_axes
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import MP_AXIS

        mp_axis = MP_AXIS if _bound_axes((MP_AXIS,)) else None
    return _CECfg(int(ignore_index), float(label_smoothing), float(z_loss),
                  ct, cv, variant, mp_axis, has_w, has_bias, bwd, fp8)


_REDUCTIONS = ("none", "mean", "sum", "mean_all")


def _token_weights(labels, ignore_index, reduction):
    """Per-token fp32 weights w_i of a reduced loss `sum_i w_i * nll_i`:
    the cotangent each token's loss gets from the reduction ("mean" over
    non-ignored tokens, "sum", "mean_all" over every token)."""
    valid = (labels != ignore_index).astype(jnp.float32)
    if reduction == "sum":
        return valid
    if reduction == "mean":
        return valid / jnp.maximum(jnp.sum(valid), 1.0)
    return valid / labels.shape[0]


def fused_linear_cross_entropy_loss(x, w, labels, bias=None, *,
                                    ignore_index=-100, label_smoothing=0.0,
                                    z_loss=0.0, chunk_tokens=0, chunk_vocab=0,
                                    variant="auto", mp_axis="auto",
                                    reduction="none"):
    """fp32 ``CE(x @ w + bias, labels)`` without the [tokens, vocab] logits.
    x: [N, H]; w: [H, V] (the local shard under bound mp); labels: [N] int.
    `reduction` "none" gives the per-token loss (ignored tokens 0); "mean"
    (over non-ignored tokens), "sum" or "mean_all" (over all N) give the
    scalar, whose gradients come out of the one pass that computes it."""
    _check_labels(labels)
    if reduction not in _REDUCTIONS:
        raise ValueError(f"reduction must be one of {_REDUCTIONS}, "
                         f"got {reduction!r}")
    cfg = _resolve_cfg(x.shape[0], w.shape[1], ignore_index, label_smoothing,
                       z_loss, chunk_tokens, chunk_vocab, variant, mp_axis,
                       True, bias is not None, reduction)
    if cfg.variant == "pallas" and bias is not None:
        cfg = cfg._replace(variant="tokens")
    one_pass = reduction != "none" and cfg.variant != "vocab"
    tw = (_token_weights(labels, ignore_index, reduction)
          if reduction != "none" else None)
    mesh = (_compat.gspmd_mesh(x, w)
            if one_pass or cfg.variant == "pallas" else None)
    if mesh is None:
        return _linear_ce(cfg, x, w, bias, labels, tw)
    # per shard, in the layout GSPMD gives the head — tokens over the data
    # axes, the vocab dim of w over "mp", which is then BOUND inside the
    # shard and selects the Megatron parallel softmax above (an undivisible
    # vocab stays whole: no mp reduction). Mosaic cannot be partitioned by
    # GSPMD, and the one pass must not be: its row statistics stay the
    # explicit pmax / psum over the vocabulary shards
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import MP_AXIS
    from paddle_tpu.distributed.mesh import shard_map_compat

    tok = _compat.mesh_axes_dividing(mesh, _compat.DATA_AXES, x.shape[0])
    voc = _compat.mesh_axes_dividing(mesh, (MP_AXIS,), w.shape[1])

    def per_shard(xs, ws, ls, *rest):
        c = _resolve_cfg(xs.shape[0], ws.shape[1], ignore_index,
                         label_smoothing, z_loss, chunk_tokens, chunk_vocab,
                         cfg.variant, MP_AXIS if voc else None, True,
                         bias is not None, reduction)
        # rest: (tw, [bias]) for the one pass; a bias never comes here
        # otherwise (its variant is "tokens", which GSPMD partitions)
        tws, bs = (rest[0], rest[1:]) if one_pass else (None, ())
        out = _linear_ce(c, xs, ws, bs[0] if bs else None, ls, tws)
        # the scalar summed over the token shards; it is equal on every mp
        # rank already (the statistics were reduced over mp)
        return jax.lax.psum(out, tok) if one_pass and tok else out

    args, specs = (x, w, labels), (P(tok, None), P(None, voc), P(tok))
    if one_pass:
        args, specs = args + (tw,), specs + (P(tok),)
    if bias is not None:
        args, specs = args + (bias,), specs + (P(voc),)
    return shard_map_compat(per_shard, mesh, specs,
                            P() if one_pass else P(tok))(*args)


def _linear_ce(cfg: _CECfg, x, w, b, labels, tw):
    """Per-token losses (tw None), or `sum_i tw_i * nll_i`: in one pass
    unless the variant is "vocab"."""
    if tw is not None and cfg.variant != "vocab":
        return _build_linear_ce_reduced(cfg)(x, w, b, labels, tw)
    nll = _build_linear_ce(cfg)(*((x, w, labels) if b is None
                                  else (x, w, b, labels)))
    return nll if tw is None else jnp.sum(tw * nll)


def softmax_cross_entropy_loss(logits, labels, *, ignore_index=-100,
                               label_smoothing=0.0, z_loss=0.0,
                               chunk_tokens=0, mp_axis="auto"):
    """Per-token fp32 softmax-CE on pre-computed (possibly vocab-sharded)
    logits [N, V_local], always token-chunked (the only variant that makes
    sense without the projection) so neither the log-softmax nor the
    backward softmax is ever materialized at [N, V]."""
    _check_labels(labels)
    cfg = _resolve_cfg(logits.shape[0], logits.shape[-1], ignore_index,
                       label_smoothing, z_loss, chunk_tokens, 0, "tokens",
                       mp_axis, False, False)
    return _build_softmax_ce(cfg)(logits, labels)
