"""Pallas paged/ragged decode-attention for TPU serving (PagedAttention).

Reference analog: the vLLM PagedAttention idea mapped onto the machinery
this repo already has — a per-sequence page table is just another
block-validity mask for the segment block-skip predicate the PR-5 flash
kernels use (`_seg_blocks_can_touch` in ops/pallas/flash_attention.py).

Layout (vLLM-style, block-granular KV cache):

  * K/V page pools: ``[num_kv_heads, num_pages, page_size, head_dim]`` —
    every page holds `page_size` consecutive tokens of ONE request.
  * page table: ``[batch, pages_per_seq]`` int32 — row b lists the pool
    pages that back request b's context, in order; unused trailing slots
    point at the reserved NULL page 0 (never handed to a request by the
    allocator, so a dead slot's DMA is harmless and compute is skipped).
  * context_lens: ``[batch]`` int32 — valid tokens per request (0 marks an
    inactive row of the fixed-size decode batch; its output is zeros).

TPU-native design: ``PrefetchScalarGridSpec`` prefetches (context_lens,
page_table) into SMEM so the K/V BlockSpec *index maps* gather pages —
grid (batch, kv_heads, pages_per_seq), one page per trailing grid step,
online-softmax state carried in VMEM scratch across the (sequential on
TPU) page dimension. GQA is native: the q block for a kv head is its
whole query-head group, K/V are never repeated.

Ragged cost: a page contributes only when the query's valid key range
[0, len-1] intersects the page's position range — literally
``_seg_blocks_can_touch(0, len-1, p*ps, p*ps+ps-1)``, THE predicate the
flash kernels share — so decode compute is O(sum_b ceil(len_b / ps))
pages, not O(batch * pages_per_seq). `page_visit_counts` runs that same
predicate as a standalone kernel = the utilization counter.

On a CPU backend the public entry point routes to a jnp gather reference
(`paged_attention_reference`, identical math) the way
F.scaled_dot_product_attention uses XLA there; `force_interpret()` pins
the exact Pallas kernel in interpret mode instead (the conftest
`paged_interpret` fixture), so tier-1 CPU runs the same kernel code the
TPU compiles through Mosaic. On a TPU backend it is always the kernel.
"""
from __future__ import annotations

import functools
import math
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas._compat import x64_off as _x64_off
from paddle_tpu.ops.pallas.flash_attention import _seg_blocks_can_touch

__all__ = ["paged_attention", "paged_decode_attention",
           "paged_attention_reference", "page_visit_counts",
           "force_interpret", "interpret_forced"]

_NEG_INF = -1e30


class _InterpretTLS(threading.local):
    def __init__(self):
        self.force = False


_interp_tls = _InterpretTLS()


@contextmanager
def force_interpret():
    """Run the paged kernels in interpret mode regardless of platform — the
    hardware-free path tier-1 uses to exercise the exact TPU kernel
    (mirrors flash_attention.force_interpret)."""
    prev = _interp_tls.force
    _interp_tls.force = True
    try:
        yield
    finally:
        _interp_tls.force = prev


def interpret_forced() -> bool:
    return _interp_tls.force


def _interpret_mode() -> bool:
    return _interp_tls.force or not _compat.on_tpu()


# ---------------------------------------------------------------------------
# decode kernel
# ---------------------------------------------------------------------------

def _decode_kernel(lens_ref, pt_ref, q_ref, k_ref, v_ref, *rest,
                   page_size: int, scale: float, pages_per_seq: int,
                   q_len: int, group: int, quantized: bool = False):
    # quantized pools ride two extra per-page scale blocks (the in-kernel
    # dequant of PR-16: bf16 K/V never materialize in HBM); the trailing
    # refs are always (o, m, l, acc)
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    first = p * page_size
    # THE shared block-skip predicate: the LAST query's valid key range is
    # [0, len+q_len-2] (verify query i sits at absolute position
    # len-1+i and may attend keys <= its own position; q_len==1 is plain
    # decode with range [0, len-1]), page p covers positions
    # [first, first+ps-1]; a page whose range can't intersect contributes
    # nothing, and len==0 rows skip ALL pages
    needed = _seg_blocks_can_touch(0, length + (q_len - 1) - 1, first,
                                   first + page_size - 1) & (length > 0)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [T*G, D]
        k = k_ref[0, 0].astype(jnp.float32)               # [PS, D]
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # fused dequant: per-slot-per-head absmax scales stream in
            # alongside the page; the bf16 values exist only in VMEM
            k = k * ks_ref[0, 0][:, None]
            v = v * vs_ref[0, 0][:, None]
        g = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [T*G, PS]
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, (g, page_size), 1)
        # per-query causal limit: query row r belongs to frame r // group
        # at absolute position length-1 + r//group -> keys < length + r//group
        # (lax.div with an explicit i32 divisor: a Python-int `//` would
        # promote to i64 under an x64-enabled outer trace in interpret mode)
        q_frame = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (g, page_size), 0),
            jnp.int32(group))
        s = jnp.where(k_pos < length + q_frame, s, jnp.float32(_NEG_INF))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == pages_per_seq - 1)
    def _finish():
        # inactive rows (len 0) never accumulated: l==0 -> output zeros
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...],
                                   jnp.float32(1e-30))).astype(o_ref.dtype)


def _check_shapes(q, k_pages, v_pages, page_table, context_lens):
    if q.ndim == 4:                     # [B, T, Hq, D] verify frame
        b, _, hq, d = q.shape
    else:
        b, hq, d = q.shape
    hkv, _, ps, dk = k_pages.shape
    if v_pages.shape != k_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    if dk != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pages {dk}")
    if hkv == 0 or hq % hkv != 0:
        raise ValueError(
            f"q heads must be a multiple of kv heads, got {hq} and {hkv}")
    if page_table.shape[0] != b or page_table.ndim != 2:
        raise ValueError(f"page_table must be [batch={b}, pages_per_seq], "
                         f"got {page_table.shape}")
    if context_lens.shape != (b,):
        raise ValueError(f"context_lens must be [batch={b}], "
                         f"got {context_lens.shape}")
    return b, hq, hkv, ps, d


def paged_decode_attention(q, k_pages, v_pages, page_table, context_lens,
                           scale: float | None = None,
                           interpret: bool | None = None,
                           k_scales=None, v_scales=None):
    """Attention over the paged KV cache (the Pallas kernel). q is either
    ``[B, Hq, D]`` (one query token per sequence — plain decode) or
    ``[B, T, Hq, D]`` (a speculative VERIFY frame: query i of row b sits at
    absolute position ``context_lens[b] - 1 + i`` and attends causally up
    to its own position, so ONE pass scores a whole draft window).
    k_pages/v_pages: [Hkv, P, page_size, D]; page_table:
    [B, pages_per_seq] int32; context_lens: [B] int32 counts committed
    context INCLUDING the frame's first (rewrite) token. Returns q's shape.

    Quantized pools: when ``k_scales``/``v_scales`` (``[Hkv, P, page_size]``
    float32 per-slot-per-head absmax scales) are given, k/v pages hold
    int8/fp8 codes and the kernel dequantizes INSIDE the grid step — the
    scale block streams alongside its page via the same index-map gather,
    so bf16 values exist only in VMEM, never in HBM.
    """
    b, hq, hkv, ps, d = _check_shapes(q, k_pages, v_pages, page_table,
                                      context_lens)
    quantized = k_scales is not None
    if quantized and v_scales is None:
        raise ValueError("k_scales given without v_scales")
    if quantized:
        want = (hkv, k_pages.shape[1], ps)
        if tuple(k_scales.shape) != want or tuple(v_scales.shape) != want:
            raise ValueError(
                f"k/v scales must be [Hkv, P, page_size]={want}, got "
                f"{tuple(k_scales.shape)} and {tuple(v_scales.shape)}")
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    t = q.shape[1]
    group = hq // hkv
    tg = t * group
    pages_per_seq = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_mode()
    # [B, T, Hkv, G, D] -> [B, Hkv, T*G, D]: the kernel's q block carries
    # the whole verify window, frame index recovered as row // group
    qg = (q.reshape(b, t, hkv, group, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, tg, d))
    kernel = functools.partial(_decode_kernel, page_size=ps, scale=scale,
                               pages_per_seq=pages_per_seq, q_len=t,
                               group=group, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1, tg, d),
                     lambda bb, h, p, lens, pt: (bb, h, 0, 0)),
        # the page gather IS the index map: scalar-prefetched page-table
        # entries pick which pool page streams into VMEM this grid step
        pl.BlockSpec((1, 1, ps, d),
                     lambda bb, h, p, lens, pt: (h, pt[bb, p], 0, 0)),
        pl.BlockSpec((1, 1, ps, d),
                     lambda bb, h, p, lens, pt: (h, pt[bb, p], 0, 0)),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # each page's scale block rides the same gather as the page itself
        in_specs += [
            pl.BlockSpec((1, 1, ps),
                         lambda bb, h, p, lens, pt: (h, pt[bb, p], 0)),
            pl.BlockSpec((1, 1, ps),
                         lambda bb, h, p, lens, pt: (h, pt[bb, p], 0)),
        ]
        operands += [jnp.asarray(k_scales, jnp.float32),
                     jnp.asarray(v_scales, jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tg, d),
                               lambda bb, h, p, lens, pt: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tg, 1), jnp.float32),
            pltpu.VMEM((tg, 1), jnp.float32),
            pltpu.VMEM((tg, d), jnp.float32),
        ],
    )
    with _x64_off():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, tg, d), q.dtype),
            interpret=interpret,
            **_compat.kernel_name("paged_decode"),
        )(jnp.asarray(context_lens, jnp.int32),
          jnp.asarray(page_table, jnp.int32), *operands)
    out = (out.reshape(b, hkv, t, group, d).transpose(0, 2, 1, 3, 4)
           .reshape(b, t, hq, d))
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# XLA reference (identical math; the off-TPU serving fast path)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pages, v_pages, page_table, context_lens,
                              scale: float | None = None,
                              k_scales=None, v_scales=None):
    """jnp gather + masked-softmax reference of `paged_decode_attention` —
    the XLA fallback the serving engine uses off-TPU (fast under jit on
    CPU, where interpret-mode Pallas would run the grid in Python).
    Accepts the same [B, Hq, D] decode and [B, T, Hq, D] verify-frame
    query layouts with identical per-query causal semantics, and the same
    optional ``k_scales``/``v_scales`` ``[Hkv, P, page_size]`` dequant
    contract as the kernel (scales applied after the f32 cast, so CPU
    tier-1 runs the exact quantized semantics)."""
    b, hq, hkv, ps, d = _check_shapes(q, k_pages, v_pages, page_table,
                                      context_lens)
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    t = q.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s_max = page_table.shape[1] * ps
    pt = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)
    # [Hkv, B, Pmax, PS, D] -> [B, Hkv, S, D]
    k = jnp.moveaxis(k_pages[:, pt], 1, 0).reshape(b, hkv, s_max, d)
    v = jnp.moveaxis(v_pages[:, pt], 1, 0).reshape(b, hkv, s_max, d)
    if k_scales is not None:
        ks = jnp.moveaxis(jnp.asarray(k_scales, jnp.float32)[:, pt],
                          1, 0).reshape(b, hkv, s_max)
        vs = jnp.moveaxis(jnp.asarray(v_scales, jnp.float32)[:, pt],
                          1, 0).reshape(b, hkv, s_max)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    qg = q.reshape(b, t, hkv, group, d).astype(jnp.float32) * scale
    s = jnp.einsum("bthgd,bhsd->bthgs", qg, k.astype(jnp.float32))
    pos = jnp.arange(s_max, dtype=jnp.int32)
    # per-query causal limit: frame i attends keys < lens + i (its own
    # absolute position lens-1+i included)
    limit = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None]   # [B, T]
    s = jnp.where(pos[None, None, None, None, :]
                  < limit[:, :, None, None, None], s, _NEG_INF)
    # inactive rows (len 0): every position masked; renormalize safely to 0
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    active = (lens > 0)[:, None, None, None, None]
    out = jnp.einsum("bthgs,bhsd->bthgd", p / jnp.maximum(denom, 1e-30),
                     v.astype(jnp.float32))
    out = jnp.where(active, out, 0.0)
    out = out.reshape(b, t, hq, d).astype(q.dtype)
    return out[:, 0] if squeeze else out


def paged_attention(q, k_pages, v_pages, page_table, context_lens,
                    scale: float | None = None,
                    k_scales=None, v_scales=None):
    """Dispatching entry point (what the model's decode path calls): the
    Pallas kernel on a TPU backend (always — a kernel failure there raises)
    or under force_interpret(); the XLA reference on a CPU backend — the
    same routing contract as F.scaled_dot_product_attention.
    ``k_scales``/``v_scales`` flow to whichever path runs (in-kernel
    dequant of quantized pools)."""
    if _compat.on_tpu() or interpret_forced():
        return paged_decode_attention(q, k_pages, v_pages, page_table,
                                      context_lens, scale=scale,
                                      k_scales=k_scales, v_scales=v_scales)
    return paged_attention_reference(q, k_pages, v_pages, page_table,
                                     context_lens, scale=scale,
                                     k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# page-visit counter (the tests' proof of the O(sum active tokens) claim)
# ---------------------------------------------------------------------------

def _visit_kernel(lens_ref, cnt_ref, *, page_size: int, pages_per_seq: int):
    b = pl.program_id(0)
    length = lens_ref[0, b]

    def body(p, n):
        first = p * page_size
        needed = _seg_blocks_can_touch(0, length - 1, first,
                                       first + page_size - 1)
        return n + needed.astype(jnp.float32)

    n = jax.lax.fori_loop(0, pages_per_seq, body, jnp.zeros((), jnp.float32))
    cnt_ref[0, 0] = n


def page_visit_counts(context_lens, page_size: int, pages_per_seq: int,
                      interpret: bool | None = None):
    """Per-sequence count of cache pages the decode kernel COMPUTES on,
    from the exact predicate it runs (`_seg_blocks_can_touch` over the page
    position range). int32 [B]; sum()/(B*pages_per_seq) is the visited
    fraction, == sum(ceil(len_b/ps)) / (B*pages_per_seq) — the serving
    tests' ragged-cost counter."""
    lens = jnp.asarray(context_lens, jnp.int32).reshape(1, -1)
    b = lens.shape[1]
    if interpret is None:
        interpret = _interpret_mode()
    kernel = functools.partial(_visit_kernel, page_size=page_size,
                               pages_per_seq=pages_per_seq)
    with _x64_off():
        cnt = pl.pallas_call(
            kernel,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, b), lambda r: (0, 0))],
            out_specs=pl.BlockSpec((1, 1), lambda r: (r, 0)),
            out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
            interpret=interpret,
            **_compat.kernel_name("paged_block_count"),
        )(lens)
    return cnt[:, 0].astype(jnp.int32)
