"""Pallas flash attention for TPU (forward + backward kernels, native GQA,
segment-aware block-sparse masking for packed sequences).

Reference analog: the vendored FlashAttention-2 CUDA kernels
(third_party/flashattn; phi/kernels/gpu/flash_attn_kernel.cu) behind
nn/functional/flash_attention.py:147.

TPU-native design: online-softmax tiling in VMEM. Forward grid =
(batch*q_heads, q_blocks); K/V stream through VMEM blocks; running (max,
denom) carried in fp32; the causal variant skips K blocks strictly above the
diagonal. Forward emits the logsumexp row stats; backward is the standard
flash-2 recurrence in two blocked kernels:

  * dq kernel — grid (BHq, q_blocks, k_blocks): dq[b,qi] accumulated in-place
    across the trailing (sequential on TPU) k-block grid dim.
  * dk/dv kernel — grid (BHkv, k_blocks, group*q_blocks): dk/dv[b,kb]
    accumulated across the trailing q-block dim, which also walks the GQA
    group so shared K/V heads see every query head.

Sequence packing (`segment_ids`, [B, S] int32): attention is block-diagonal
per document. Inside a block the kernel masks `q_seg[i] != k_seg[j]` at the
same point the causal mask applies; ACROSS blocks it skips any K block whose
segment-id range cannot intersect the Q block's (per-block min/max — packed
rows carry non-decreasing segment ids so ranges are tight), composed with the
causal diagonal skip. Per-document attention cost is therefore
O(sum_i len_i^2), not O(S^2). All three kernels (fwd, dq, dkv) share ONE
skip predicate, `_seg_blocks_can_touch`; `segment_block_visit_counts` runs
that same predicate as a standalone Pallas kernel so benchmarks can count
exactly which K blocks the attention kernels visit.

Peak memory is O(block * D) per grid step — no [S, S] materialization in
either direction. GQA is handled by BlockSpec index maps (q-head -> kv-head
= h // group), never by materializing repeated K/V.

Runs through Mosaic on a TPU backend and in interpreter mode on a CPU
backend, so the same code path is unit-tested on CPU (the fake-device
pattern, SURVEY §4.4); `force_interpret()` pins interpret mode explicitly
(the conftest fixture the tier-1 segment tests use). On a TPU backend a
kernel that fails to trace, lower or compile raises — nothing here or in
the callers retries in interpret mode or on an XLA path.
"""
from __future__ import annotations

import functools
import math
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas._compat import kernel_trace_ctx as _kernel_trace_ctx

__all__ = ["flash_attention_bshd", "flash_attention_bhsd",
           "segment_block_visit_counts", "pallas_blocks_ok",
           "force_interpret"]

_NEG_INF = -1e30


class _InterpretTLS(threading.local):
    def __init__(self):
        self.force = False


_interp_tls = _InterpretTLS()


@contextmanager
def force_interpret():
    """Run the Pallas kernels in interpret mode regardless of platform — the
    hardware-free path the tier-1 suite uses to exercise the exact kernel
    code (incl. the segment block-skip predicate) the TPU runs."""
    prev = _interp_tls.force
    _interp_tls.force = True
    try:
        yield
    finally:
        _interp_tls.force = prev


def _interpret_mode() -> bool:
    return _interp_tls.force or not _compat.on_tpu()


def interpret_forced() -> bool:
    """True inside a `force_interpret()` block — callers with their own XLA
    fallback (F.scaled_dot_product_attention) route into the Pallas kernels
    off-TPU only when the tests ask for it explicitly."""
    return _interp_tls.force


def _seg_blocks_can_touch(q_min, q_max, k_min, k_max):
    """THE cross-block skip predicate: a K block may contribute to a Q block
    only if their segment-id RANGES intersect (conservative for arbitrary
    ids; exact for the packer's per-row non-decreasing ids). Shared by the
    forward, dq, and dk/dv kernels and by the visit-count kernel, so the
    benchmark counter provably counts what the attention kernels execute."""
    return jnp.logical_and(k_min <= q_max, k_max >= q_min)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int, causal: bool,
                scale: float, seq_len: int, block_q: int, segmented: bool,
                block_skip: bool):
    if segmented:
        qseg_ref, kseg_ref, o_ref, lse_ref = rest
    else:
        qseg_ref = kseg_ref = None
        o_ref, lse_ref = rest
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    bq = q.shape[0]
    if segmented:
        q_seg = qseg_ref[...]                       # [1, BQ] int32
        q_seg_col = q_seg.reshape(bq, 1)
        q_min = jnp.min(q_seg)
        q_max = jnp.max(q_seg)

    num_kb = seq_len // block_k
    if causal:
        # process K blocks up to and including the diagonal block of this Q tile
        last = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        last = num_kb

    def k_seg_block(kb):                            # [1, BK] int32
        # the ids sit along LANES, where Mosaic only takes a dynamic start
        # it can prove a multiple of 128. Sequences under 128 (the serving
        # engine's 64-row pack frame) are one whole K block: load it static
        if num_kb == 1:
            return kseg_ref[...]
        return kseg_ref[:, pl.ds(pl.multiple_of(kb * block_k, block_k),
                                 block_k)]

    def compute(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if segmented:
            s = jnp.where(q_seg_col == k_seg_block(kb), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l, acc

    if segmented and block_skip:
        def body(kb, carry):
            k_seg_blk = k_seg_block(kb)
            needed = _seg_blocks_can_touch(q_min, q_max,
                                           jnp.min(k_seg_blk),
                                           jnp.max(k_seg_blk))
            return jax.lax.cond(needed, lambda c: compute(kb, c),
                                lambda c: c, carry)
    else:
        body = compute

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)   # v's width, not q's
    m, l, acc = jax.lax.fori_loop(0, last, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)


def _validated_block(v, which, seq_len, prefix="flash_block"):
    v = int(v)
    if v <= 0 or seq_len % min(v, seq_len) != 0:
        raise ValueError(
            f"FLAGS_{prefix}_{which}={v} must be a positive divisor "
            f"of seq_len={seq_len} (grid tiling would drop positions)")
    return min(v, seq_len)


def _heuristic_blocks(seq_len: int):
    # swept end-to-end on v5e at seq 2048 (round 3): (512, 1024) beats the
    # old (256, 512) default by ~7% MFU (0.725 -> 0.778)
    bq = next((b for b in (512, 256, 128) if seq_len % b == 0), seq_len)
    bk = next((b for b in (1024, 512, 128) if seq_len % b == 0), seq_len)
    return min(bq, seq_len), min(bk, seq_len)


def _make_validate(seq_len: int, prefix: str):
    def validate(values, geometry):
        _validated_block(values["block_q"], "q", seq_len, prefix)
        _validated_block(values["block_k"], "k", seq_len, prefix)

    return validate


def _pick_blocks(seq_len: int):
    """Forward Q/K tiles through the shared resolver (FLAGS override >
    tuning-cache hit > heuristic; the once-duplicated partial-override
    warn branch now lives in tuning.blocks.resolve_blocks)."""
    from paddle_tpu.tuning.blocks import resolve_blocks

    res = resolve_blocks("flash_fwd", {"seq_len": seq_len},
                         default=lambda g: _heuristic_blocks(seq_len),
                         validate=_make_validate(seq_len, "flash_block"))
    bq, bk = res.as_tuple()
    return min(bq, seq_len), min(bk, seq_len)


def _pick_blocks_bwd(seq_len: int):
    """Backward kernels tile independently of the forward (different
    arithmetic intensity); FLAGS_flash_bwd_block_q/k override, tuned
    'flash_bwd' entries next, forward picks as the default."""
    from paddle_tpu.tuning.blocks import resolve_blocks

    res = resolve_blocks("flash_bwd", {"seq_len": seq_len},
                         default=lambda g: _pick_blocks(seq_len),
                         validate=_make_validate(seq_len,
                                                 "flash_bwd_block"))
    bq, bk = res.as_tuple()
    return min(bq, seq_len), min(bk, seq_len)


def pallas_blocks_ok(seq_len: int):
    """(ok, reason): validate that the flag-chosen forward AND backward block
    sizes divide `seq_len`. Callers with an XLA fallback (e.g.
    F.scaled_dot_product_attention) check this BEFORE entering Pallas so a
    bad FLAGS_flash_block_q/k override degrades to the fallback with a
    warning instead of failing inside the kernel launch."""
    try:
        _pick_blocks(seq_len)
        _pick_blocks_bwd(seq_len)
        return True, None
    except ValueError as e:
        return False, str(e)


def _block_skip_enabled() -> bool:
    from paddle_tpu.core.flags import flag

    try:
        return bool(flag("flash_segment_block_skip"))
    except KeyError:  # pragma: no cover - flags module always defines it
        return True


def _flash_fwd(q, k, v, seg, causal: bool, scale: float, group: int,
               heads_q: int, interpret: bool):
    """q: [BHq, S, D]; k: [BHkv, S, D]; v: [BHkv, S, Dv] with BHq ==
    BHkv*group (Dv may differ from D: latent attention's 192 / 128);
    seg: [B, S] int32 or None -> (out [BHq, S, Dv], lse)."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    block_q, block_k = _pick_blocks(s)
    grid = (bh, s // block_q)
    segmented = seg is not None
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        seq_len=s, block_q=block_q, segmented=segmented,
        block_skip=_block_skip_enabled(),
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i: (b // group, 0, 0)),
        pl.BlockSpec((1, s, dv), lambda b, i: (b // group, 0, 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs.append(pl.BlockSpec((1, block_q),
                                     lambda b, i: (b // heads_q, i)))
        in_specs.append(pl.BlockSpec((1, s), lambda b, i: (b // heads_q, 0)))
        args.extend([seg, seg])
    # Mosaic lowering mishandles 64-bit index types; the kernel is pure
    # f32/bf16/i32, so trace it with x64 off regardless of the global setting.
    # Interpret mode keeps the ambient x64 (see kernel_trace_ctx): an outer
    # jit lowers the grid loops after this context exits, and an x32-traced /
    # x64-lowered jaxpr trips the StableHLO verifier on weak int literals.
    # the forward keeps a head's whole K and V in VMEM, twice (the pipeline's
    # two buffers). Past the compiler's own 16 MiB scope (8192 x 192 + 128:
    # 12 MiB, refused) the call asks for what it needs; below it, as at
    # 4096 x 128, nothing is passed and the call compiles as it always has
    lanes = _compat.lanes
    resident = 2 * s * (lanes(d) + lanes(dv)) * q.dtype.itemsize
    params = {}
    if resident > 8 * 2**20 and not interpret:
        from jax.experimental.pallas import tpu as pltpu

        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(resident + 24 * 2**20, 100 * 2**20))
    with _kernel_trace_ctx(interpret):
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            **params,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
            ],
            interpret=interpret,
            **_compat.kernel_name("flash_fwd"),
        )(*args)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward kernels (flash-2 recurrence from saved lse; no S^2 anywhere)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale: float, causal: bool, block_q: int, block_k: int,
               segmented: bool, block_skip: bool):
    if segmented:
        qseg_ref, kseg_ref, dq_ref = rest
    else:
        qseg_ref = kseg_ref = None
        (dq_ref,) = rest
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    # causal: K blocks strictly above the diagonal contribute nothing;
    # segments: K blocks whose id range misses the Q block's contribute
    # nothing either (the SAME predicate the forward skip uses)
    needed = True
    if causal:
        needed = kb * block_k <= (qi + 1) * block_q - 1
    if segmented and block_skip:
        seg_ok = _seg_blocks_can_touch(
            jnp.min(qseg_ref[...]), jnp.max(qseg_ref[...]),
            jnp.min(kseg_ref[...]), jnp.max(kseg_ref[...]))
        needed = jnp.logical_and(needed, seg_ok)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [BQ, D]
        k_blk = k_ref[0].astype(jnp.float32)      # [BK, D]
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                          # [BQ, 1]
        delta = delta_ref[0]                      # [BQ, 1]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bq = q.shape[0]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if segmented:
            s = jnp.where(qseg_ref[...].reshape(bq, 1) == kseg_ref[...],
                          s, _NEG_INF)
        p = jnp.exp(s - lse)                      # [BQ, BK]
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_ref[0] += jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale: float, causal: bool, block_q: int, block_k: int,
                q_blocks: int, segmented: bool, block_skip: bool):
    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref = rest
    else:
        qseg_ref = kseg_ref = None
        dk_ref, dv_ref = rest
    kb = pl.program_id(1)
    qj = pl.program_id(2)           # walks group-major over (group, q_blocks)
    qi = qj % q_blocks              # q-block index within the query head

    @pl.when(qj == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    needed = True
    if causal:
        # whole q block above the diagonal w.r.t. this k block -> no contribution
        needed = (qi + 1) * block_q - 1 >= kb * block_k
    if segmented and block_skip:
        seg_ok = _seg_blocks_can_touch(
            jnp.min(qseg_ref[...]), jnp.max(qseg_ref[...]),
            jnp.min(kseg_ref[...]), jnp.max(kseg_ref[...]))
        needed = jnp.logical_and(needed, seg_ok)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [BQ, D]
        k_blk = k_ref[0].astype(jnp.float32)      # [BK, D]
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bq = q.shape[0]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if segmented:
            s = jnp.where(qseg_ref[...].reshape(bq, 1) == kseg_ref[...],
                          s, _NEG_INF)
        p = jnp.exp(s - lse)                      # [BQ, BK]
        dv_ref[0] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[0] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)


def _flash_bwd(q, k, v, seg, out, lse, do, causal: bool, scale: float,
               group: int, heads_q: int, interpret: bool):
    """Blocked flash-2 backward. q/do/out/lse: [BHq, ...]; k/v: [BHkv, ...];
    seg: [B, S] int32 or None."""
    bhq, s, d = q.shape
    dv = v.shape[-1]
    bhkv = k.shape[0]
    heads_kv = heads_q // group
    block_q, block_k = _pick_blocks_bwd(s)
    q_blocks, k_blocks = s // block_q, s // block_k
    segmented = seg is not None
    block_skip = _block_skip_enabled()
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)                       # [BHq, S, 1]
    lse3 = lse[..., None]                                # [BHq, S, 1]

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_args = [q, k, v, do, lse3, delta]
    if segmented:
        dq_in_specs.append(pl.BlockSpec(
            (1, block_q), lambda b, i, j: (b // heads_q, i)))
        dq_in_specs.append(pl.BlockSpec(
            (1, block_k), lambda b, i, j: (b // heads_q, j)))
        dq_args.extend([seg, seg])

    with _kernel_trace_ctx(interpret):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              segmented=segmented, block_skip=block_skip),
            grid=(bhq, q_blocks, k_blocks),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bhq, s, d), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("flash_dq"),
        )(*dq_args)

        # trailing grid dim walks (group, q_blocks) group-major so each kv head
        # accumulates contributions from every query head in its GQA group
        dkv_in_specs = [
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, qj: (b * group + qj // q_blocks, qj % q_blocks, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, qj: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, j, qj: (b, j, 0)),
            pl.BlockSpec((1, block_q, dv),
                         lambda b, j, qj: (b * group + qj // q_blocks, qj % q_blocks, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, qj: (b * group + qj // q_blocks, qj % q_blocks, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, qj: (b * group + qj // q_blocks, qj % q_blocks, 0)),
        ]
        dkv_args = [q, k, v, do, lse3, delta]
        if segmented:
            dkv_in_specs.append(pl.BlockSpec(
                (1, block_q),
                lambda b, j, qj: (b // heads_kv, qj % q_blocks)))
            dkv_in_specs.append(pl.BlockSpec(
                (1, block_k), lambda b, j, qj: (b // heads_kv, j)))
            dkv_args.extend([seg, seg])
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              q_blocks=q_blocks, segmented=segmented,
                              block_skip=block_skip),
            grid=(bhkv, k_blocks, group * q_blocks),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, qj: (b, j, 0)),
                pl.BlockSpec((1, block_k, dv), lambda b, j, qj: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bhkv, s, d), jnp.float32),
                jax.ShapeDtypeStruct((bhkv, s, dv), jnp.float32),
            ],
            interpret=interpret,
            **_compat.kernel_name("flash_dkv"),
        )(*dkv_args)

    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# block-visit counter (the tests' proof of the sparsity claim)
# ---------------------------------------------------------------------------

def _visit_kernel(seg_ref, cnt_ref, *, block_q: int, block_k: int,
                  seq_len: int, causal: bool):
    qi = pl.program_id(1)
    q_seg = seg_ref[:, pl.ds(qi * block_q, block_q)]
    q_min = jnp.min(q_seg)
    q_max = jnp.max(q_seg)
    num_kb = seq_len // block_k
    if causal:
        last = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        last = num_kb

    def body(kb, n):
        k_seg = seg_ref[:, pl.ds(kb * block_k, block_k)]
        needed = _seg_blocks_can_touch(q_min, q_max,
                                       jnp.min(k_seg), jnp.max(k_seg))
        return n + needed.astype(jnp.float32)

    n = jax.lax.fori_loop(0, last, body, jnp.zeros((), jnp.float32))
    cnt_ref[0, 0, 0] = n


def segment_block_visit_counts(segment_ids, block_q: int | None = None,
                               block_k: int | None = None,
                               causal: bool = True,
                               interpret: bool | None = None):
    """Per-(row, q-block) count of K blocks the segment-aware kernels VISIT,
    computed by running the forward kernel's exact skip predicate
    (`_seg_blocks_can_touch` + the causal diagonal bound) as its own Pallas
    kernel. Returns int32 [B, q_blocks]; sum()/total_blocks is the visited
    fraction a packed batch visits (~sum len_i^2 / S^2 under
    packing vs ~1/2 causal dense)."""
    seg = jnp.asarray(segment_ids, jnp.int32)
    b, s = seg.shape
    if block_q is None or block_k is None:
        bq, bk = _pick_blocks(s)
        block_q = block_q or bq
        block_k = block_k or bk
    if interpret is None:
        interpret = _interpret_mode()
    kernel = functools.partial(_visit_kernel, block_q=block_q,
                               block_k=block_k, seq_len=s, causal=causal)
    with _kernel_trace_ctx(interpret):
        cnt = pl.pallas_call(
            kernel,
            grid=(b, s // block_q),
            in_specs=[pl.BlockSpec((1, s), lambda r, i: (r, 0))],
            out_specs=pl.BlockSpec((1, 1, 1), lambda r, i: (r, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, s // block_q, 1), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("flash_block_count"),
        )(seg)
    return cnt[..., 0].astype(jnp.int32)


# ---------------------------------------------------------------------------
# custom-vjp wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q, k, v, causal, scale, group, interpret):
    out, _ = _flash_fwd(q, k, v, None, causal, scale, group, group, interpret)
    return out


def _flash3_fwd(q, k, v, causal, scale, group, interpret):
    out, lse = _flash_fwd(q, k, v, None, causal, scale, group, group,
                          interpret)
    return out, (q, k, v, out, lse)


def _flash3_bwd(causal, scale, group, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, None, out, lse, do, causal, scale,
                            group, group, interpret)
    return dq, dk, dv


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash3_seg(q, k, v, seg, causal, scale, group, heads_q, interpret):
    out, _ = _flash_fwd(q, k, v, seg, causal, scale, group, heads_q,
                        interpret)
    return out


def _flash3_seg_fwd(q, k, v, seg, causal, scale, group, heads_q, interpret):
    out, lse = _flash_fwd(q, k, v, seg, causal, scale, group, heads_q,
                          interpret)
    return out, (q, k, v, seg, out, lse)


def _flash3_seg_bwd(causal, scale, group, heads_q, interpret, res, do):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, seg, out, lse, do, causal, scale,
                            group, heads_q, interpret)
    return dq, dk, dv, None  # integer segment ids carry no cotangent


_flash3_seg.defvjp(_flash3_seg_fwd, _flash3_seg_bwd)


def flash_attention_bhsd(q, k, v, causal: bool = False,
                         scale: float | None = None, segment_ids=None,
                         interpret: bool | None = None):
    """q: [B, Hq, S, D]; k: [B, Hkv, S, D]; v: [B, Hkv, S, Dv] with
    Hq % Hkv == 0 (GQA/MQA). Dv may differ from D; the output is v's width.
    segment_ids: [B, S] int32 packed-document ids (attention is then
    block-diagonal per document, with whole K blocks skipped when no segment
    overlaps the Q block)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hkv == 0 or hq % hkv != 0:
        raise ValueError(
            f"q heads must be a multiple of kv heads, got {hq} and {hkv}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_mode()
    seg = None
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids, jnp.int32)
        if seg.shape != (b, s):
            raise ValueError(
                f"segment_ids must be [batch, seq]=({b}, {s}), "
                f"got {seg.shape}")

    def kernels(q, k, v, *seg):
        b, hq, hkv = q.shape[0], q.shape[1], k.shape[1]   # this shard's
        q3 = q.reshape(b * hq, s, d)
        k3 = k.reshape(b * hkv, s, d)
        v3 = v.reshape(b * hkv, s, v.shape[-1])
        if seg:
            out = _flash3_seg(q3, k3, v3, seg[0], causal, scale, group, hq,
                              interpret)
        else:
            out = _flash3(q3, k3, v3, causal, scale, group, interpret)
        return out.reshape(b, hq, s, v.shape[-1])

    args = (q, k, v) + (() if seg is None else (seg,))
    mesh = _compat.gspmd_mesh(q, k, v)
    if mesh is None:
        return kernels(*args)
    # Mosaic under a GSPMD mesh: per shard, in the layout GSPMD gives
    # attention operands — batch over the data axes, heads over "mp"
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import MP_AXIS
    from paddle_tpu.distributed.mesh import shard_map_compat

    bax = _compat.mesh_axes_dividing(mesh, _compat.DATA_AXES, b)
    hax = _compat.mesh_axes_dividing(mesh, (MP_AXIS,), hq, hkv)
    qkv = P(bax, hax, None, None)
    return shard_map_compat(
        kernels, mesh, (qkv,) * 3 + (P(bax, None),) * (seg is not None),
        qkv)(*args)


def flash_attention_bshd(q, k, v, causal: bool = False,
                         scale: float | None = None, segment_ids=None,
                         interpret: bool | None = None):
    """q,k,v: [B, S, H, D] (paddle flash-attention layout); GQA via H_kv < H_q."""
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qh, kh, vh, causal=causal, scale=scale,
                               segment_ids=segment_ids, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
