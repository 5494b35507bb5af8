"""Kimi Delta Attention (KDA): the gated delta rule with a decay of its own
for every channel, in chunkwise (WY / UT-transform) form.

The recurrence, a head at a time, `S` is [K, V] and starts at zero:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   o_t = S_t^T q_t

with `a_t = exp(g_t)` (g_t <= 0 a channel) and `b_t` in (0, 1). Written as a
delta rule, `S_t = Diag(a_t) S_{t-1} + k_t u_t^T` with the pseudo-value
`u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`. Over a chunk of C tokens that
starts from the state S, with G_t the running sum of g inside the chunk:

    A[t, i] = b_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])     (i < t)
    T       = (I + A)^-1                 (unit lower triangular, C x C)
    Ubar    = T (b * v),   Wbar = T (b * exp(G) * k)
    U       = Ubar - Wbar S                                    (the part that needs S)
    P[t, i] = sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])         (i <= t)
    O       = (exp(G) * q) S + P U
    S'      = Diag(exp(G_C)) S + (k * exp(G_C - G))^T U

Everything that does not need S (`A`, `T`, `Ubar`, `Wbar`, `P` and the
decayed copies of q and k) is the same for every chunk at once and is plain
XLA: batched products that JAX differentiates itself. The three lines that
need S are the sequential part and are the Pallas kernels here: `kda_fwd`
walks the chunks of a block of heads with S in VMEM, `kda_bwd` walks them
backwards with dS. The decays are summed and exponentiated in float32, in
log space, about the middle of the chunk (`exp(G_t - G_mid)`,
`exp(G_mid - G_i)`), so that a chunk's whole decay may reach exp(-80)
before a factor saturates; T is applied in float32 by forward substitution
(a triangular solve; the Neumann product of six factors cancels to NaN once
the keys are correlated). Products take bfloat16 operands where the
model's type is bfloat16 and always accumulate in float32.

The token-by-token recurrence is never the timed path; it is the
benchmark's reference (`benchmark/arch/kimi_linear/reference.py`) and the
tests'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _compat
from paddle_tpu.ops.pallas.flash_attention import _interpret_mode

__all__ = ["kda_chunked", "CHUNK", "KDA_OUT", "resolve_head_block"]

CHUNK = 64
# the `checkpoint_name` of `kda_chunked`'s result: what a caller's
# `save_only_these_names` policy keeps it by
KDA_OUT = "kda_out"
_HI = jax.lax.Precision.HIGHEST
_EXP_CAP = 80.0


def resolve_head_block(n_heads: int, n_chunks: int = 0, width: int = 128) -> int:
    """Heads a grid step of the scan kernels walks together (their products
    are independent, so the scheduler overlaps them). Static a compiled
    program: recorded at trace time as `last_resolution("kda")`, the chunk
    and the block shapes under `derived`."""
    from paddle_tpu.tuning.blocks import Resolution, note_derived

    hb = next(b for b in (4, 2, 1) if n_heads % b == 0)
    note_derived(Resolution("kda", {"chunk": CHUNK, "head_block": hb},
                            "default", "heuristic"),
                 grid=(n_heads // hb, n_chunks), state_block=(hb, width, width),
                 chunk_block=(hb, 1, CHUNK, width))
    return hb


# ---------------------------------------------------------------------------
# the sequential part: Pallas kernels over (blocks of heads, chunks)
# ---------------------------------------------------------------------------

def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(qd_ref, w_ref, u_ref, pm_ref, kdt_ref, gam_ref,
                o_ref, unew_ref, s0_ref, s_scr, *, heads: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(heads):
        s = s_scr[h]                                           # [K, V] f32
        s0_ref[h, 0] = s
        sl = s.astype(w_ref.dtype)
        u = u_ref[h, 0].astype(jnp.float32) - _dot(w_ref[h, 0], sl)   # [C, V]
        ul = u.astype(w_ref.dtype)
        unew_ref[h, 0] = ul
        o = _dot(qd_ref[h, 0], sl) + _dot(pm_ref[h, 0], ul)
        o_ref[h, 0] = o.astype(o_ref.dtype)
        s_scr[h] = gam_ref[h, 0] * s + _dot(kdt_ref[h, 0], ul)  # gam [K, 1]


def _bwd_kernel(qdt_ref, wt_ref, w_ref, pmt_ref, kd_ref, gam_ref, s0_ref,
                unew_ref, do_ref,
                dqd_ref, dw_ref, du_ref, dpm_ref, dkd_ref, dgam_ref, ds_scr,
                *, heads: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    nt = ((1,), (1,))
    for h in range(heads):
        ds1 = ds_scr[h]                                        # dL/dS' [K, V]
        s = s0_ref[h, 0]
        lo = w_ref.dtype
        sl, ds1l = s.astype(lo), ds1.astype(lo)
        do = do_ref[h, 0]
        u = unew_ref[h, 0]
        du = _dot(pmt_ref[h, 0], do) + _dot(kd_ref[h, 0], ds1l)       # [C, V]
        dul = du.astype(lo)
        du_ref[h, 0] = du.astype(du_ref.dtype)
        dqd_ref[h, 0] = _dot(do, sl, nt).astype(dqd_ref.dtype)        # dO S^T
        dw_ref[h, 0] = (-_dot(dul, sl, nt)).astype(dw_ref.dtype)
        dpm_ref[h, 0] = _dot(do, u, nt).astype(dpm_ref.dtype)         # dO U^T
        dkd_ref[h, 0] = _dot(u, ds1l, nt).astype(dkd_ref.dtype)       # U dS'^T
        dgam_ref[h, 0] = jnp.sum(ds1 * s, axis=1, keepdims=True)
        ds_scr[h] = (gam_ref[h, 0] * ds1 + _dot(qdt_ref[h, 0], do)
                     - _dot(wt_ref[h, 0], dul))


def _spec(hb, *tail, reverse_of=None):
    """One chunk of `hb` heads of an operand [BH, NC, *tail]."""
    zeros = (0,) * len(tail)
    if reverse_of is None:
        return pl.BlockSpec((hb, 1) + tail, lambda b, c: (b, c) + zeros)
    return pl.BlockSpec((hb, 1) + tail,
                        lambda b, c: (b, reverse_of - 1 - c) + zeros)


def _scan_fwd(qd, w, u, pm, kdt, gam, hb, interpret):
    bh, nc, c, k = qd.shape
    v = u.shape[-1]
    kernel = functools.partial(_fwd_kernel, heads=hb)
    with _compat.kernel_trace_ctx(interpret):
        return pl.pallas_call(
            kernel,
            grid=(bh // hb, nc),
            in_specs=[_spec(hb, c, k), _spec(hb, c, k), _spec(hb, c, v),
                      _spec(hb, c, c), _spec(hb, k, c), _spec(hb, k, 1)],
            out_specs=[_spec(hb, c, v), _spec(hb, c, v), _spec(hb, k, v)],
            out_shape=[jax.ShapeDtypeStruct((bh, nc, c, v), u.dtype),
                       jax.ShapeDtypeStruct((bh, nc, c, v), u.dtype),
                       jax.ShapeDtypeStruct((bh, nc, k, v), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((hb, k, v), jnp.float32)],
            interpret=interpret,
            **_compat.kernel_name("kda_fwd"),
        )(qd, w, u, pm, kdt, gam)


def _scan_bwd(qd, w, pm, kdt, gam, s0, unew, do, hb, interpret):
    bh, nc, c, k = qd.shape
    v = do.shape[-1]
    kernel = functools.partial(_bwd_kernel, heads=hb)
    t = lambda x: jnp.swapaxes(x, -1, -2)       # noqa: E731  (XLA transposes)
    r = dict(reverse_of=nc)
    lo = qd.dtype
    with _compat.kernel_trace_ctx(interpret):
        return pl.pallas_call(
            kernel,
            grid=(bh // hb, nc),
            in_specs=[_spec(hb, k, c, **r), _spec(hb, k, c, **r),
                      _spec(hb, c, k, **r), _spec(hb, c, c, **r),
                      _spec(hb, c, k, **r), _spec(hb, k, 1, **r),
                      _spec(hb, k, v, **r), _spec(hb, c, v, **r),
                      _spec(hb, c, v, **r)],
            out_specs=[_spec(hb, c, k, **r), _spec(hb, c, k, **r),
                       _spec(hb, c, v, **r), _spec(hb, c, c, **r),
                       _spec(hb, c, k, **r), _spec(hb, k, 1, **r)],
            out_shape=[jax.ShapeDtypeStruct((bh, nc, c, k), lo),
                       jax.ShapeDtypeStruct((bh, nc, c, k), lo),
                       jax.ShapeDtypeStruct((bh, nc, c, v), lo),
                       jax.ShapeDtypeStruct((bh, nc, c, c), lo),
                       jax.ShapeDtypeStruct((bh, nc, c, k), lo),
                       jax.ShapeDtypeStruct((bh, nc, k, 1), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((hb, k, v), jnp.float32)],
            interpret=interpret,
            **_compat.kernel_name("kda_bwd"),
        )(t(qd), t(w), w, t(pm), t(kdt), gam, s0, unew, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(qd, w, u, pm, kdt, gam, hb, interpret):
    return _scan_fwd(qd, w, u, pm, kdt, gam, hb, interpret)[0]


def _scan_vjp_fwd(qd, w, u, pm, kdt, gam, hb, interpret):
    o, unew, s0 = _scan_fwd(qd, w, u, pm, kdt, gam, hb, interpret)
    return o, (qd, w, pm, kdt, gam, s0, unew)


def _scan_vjp_bwd(hb, interpret, res, do):
    qd, w, pm, kdt, gam, s0, unew = res
    dqd, dw, du, dpm, dkd, dgam = _scan_bwd(qd, w, pm, kdt, gam, s0, unew,
                                            do.astype(qd.dtype), hb, interpret)
    return (dqd, dw, du, dpm, jnp.swapaxes(dkd, -1, -2),
            dgam.astype(gam.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


# ---------------------------------------------------------------------------
# the part every chunk does at once: plain XLA, differentiated by JAX
# ---------------------------------------------------------------------------

def _solve_unit_lower(a, rhs):
    """(I + A)^-1 rhs for strictly lower triangular A [..., C, C], float32,
    by forward substitution (XLA's triangular solve). The Neumann series
    (I - A)(I + A^2)(I + A^4)... is the same matrix on paper and is NOT
    used: keys that come out of a SiLU point the same way, A's entries are
    then b * 0.5 and not b * 0.05, its powers pass 1e30 before they vanish
    and the series cancels to NaN in float32 (my chip run, PR 27)."""
    from jax.scipy.linalg import solve_triangular

    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    with jax.default_matmul_precision("highest"):
        return solve_triangular(eye + a, rhs, lower=True, unit_diagonal=True)


def _chunked(q, k, v, g, beta, hb, interpret):
    """q, k [BH, T, K]; v [BH, T, V]; g [BH, T, K] float32 log-decays;
    beta [BH, T] float32. T a multiple of CHUNK."""
    bh, t, kd = q.shape
    c, nc, lo = CHUNK, t // CHUNK, q.dtype
    f32 = jnp.float32
    ch = lambda x: x.reshape(bh, nc, c, *x.shape[2:])    # noqa: E731
    q, k, v, g, beta = ch(q), ch(k), ch(v), ch(g.astype(f32)), ch(beta.astype(f32))
    gc = jnp.cumsum(g, axis=2)                           # G_t, [BH, NC, C, K]
    mid = gc[:, :, c // 2 - 1: c // 2]                   # about the chunk's middle
    up = jnp.exp(jnp.minimum(gc - mid, _EXP_CAP))        # exp(G_t - G_mid)
    down = jnp.exp(jnp.minimum(mid - gc, _EXP_CAP))      # exp(G_mid - G_i)
    last = gc[:, :, -1:]
    kf, qf = k.astype(f32), q.astype(f32)
    k_up, k_down, q_up = (kf * up).astype(lo), (kf * down).astype(lo), (qf * up).astype(lo)
    ein = functools.partial(jnp.einsum, preferred_element_type=f32)
    tri = jnp.tril(jnp.ones((c, c), bool))
    a = beta[..., None] * ein("bntk,bnik->bnti", k_up, k_down)
    a = jnp.where(tri & ~jnp.eye(c, dtype=bool), a, 0.0)
    # Ubar | Wbar = T [b v | b exp(G) k], one solve for both
    rhs = beta[..., None] * jnp.concatenate([v.astype(f32), kf * jnp.exp(gc)], axis=-1)
    solved = _solve_unit_lower(a, rhs)
    ubar, wbar = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    pm = jnp.where(tri, ein("bntk,bnik->bnti", q_up, k_down), 0.0)
    qd = (qf * jnp.exp(gc)).astype(lo)                   # exp(G) * q
    kdt = jnp.swapaxes((kf * jnp.exp(last - gc)).astype(lo), -1, -2)
    gam = jnp.swapaxes(jnp.exp(last), -1, -2)            # [BH, NC, K, 1]
    o = _scan(qd, wbar.astype(lo), ubar.astype(lo), pm.astype(lo), kdt, gam,
              hb, interpret)
    return o.reshape(bh, t, -1)


def kda_chunked(q, k, v, g, beta, *, interpret: bool | None = None):
    """o_t of the recurrence above for q, k [B, T, H, K], v [B, T, H, V],
    g [B, T, H, K] (log-decay, <= 0, float32) and beta [B, T, H]; S_0 = 0.
    q and k arrive as the layer means them (normalised, q scaled). Returns
    [B, T, H, V] in v's type. Any T: the tail is padded with tokens that
    leave the state alone (k = 0, beta = 0, g = 0). What the chunks hold is
    made again in the backward pass, a block of heads at a time
    (`jax.checkpoint`): of this call only the arguments are kept. The result
    carries the name `KDA_OUT`: a caller that recomputes its own forward
    (models/kimi_linear.py) keeps it by that name, 2 * B * T * H * V bytes in
    bfloat16, and so does not run the chunks a third time for it. Outside
    such a policy the name does nothing."""
    b, t, h, kd = q.shape
    if interpret is None:
        interpret = _interpret_mode()
    pad = (-t) % CHUNK
    hb = resolve_head_block(b * h, (t + pad) // CHUNK, kd)

    def heads_first(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, t, *x.shape[3:])
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) if pad else x

    # a block of heads at a time, one after the other: what the chunks of
    # ALL heads hold at once (a dozen [tokens, heads * K] float32 arrays and
    # the states) is several GB at 16k tokens; a block's is a sixteenth
    run = jax.checkpoint(functools.partial(_chunked, hb=hb, interpret=interpret))
    blocks = [heads_first(x) for x in (q, k, v, g, beta)]
    blocks = [x.reshape(b * h // hb, hb, *x.shape[1:]) for x in blocks]
    o = jax.lax.map(lambda xs: run(*xs), tuple(blocks))
    o = o.reshape(b * h, t + pad, -1)[:, :t].reshape(b, h, t, -1)
    return checkpoint_name(jnp.moveaxis(o, 1, 2).astype(v.dtype), KDA_OUT)
