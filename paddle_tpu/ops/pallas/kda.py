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
decayed copies of q and k) is the same for every chunk at once: the kernels
`kda_chunk_fwd` / `kda_chunk_bwd` build it a block of heads and two chunks
at a time with every intermediate in VMEM, and the backward kernel makes the
chunk again and takes its gradient by hand (no intermediate goes through
HBM). The three lines that need S are the sequential part: `kda_fwd` walks
the chunks of a block of heads with S in VMEM, `kda_bwd` walks them
backwards with dS. The decays are summed and exponentiated in float32, in
log space, about the middle of the chunk (`exp(G_t - G_mid)`,
`exp(G_mid - G_i)`), so that a chunk's whole decay may reach exp(-80)
before a factor saturates. T is float32: the recursive (doubling) form of
the triangular solve, `T21 = -T22 A21 T11` over blocks of 1, 2, ..., 32
rows, then two Newton steps with float32 residuals (the Neumann product of
six factors of A cancels to NaN once the keys are correlated, and is not
used). A float32 product is the six bfloat16 passes that XLA's
`Precision.HIGHEST` makes on a TPU, as one product over the operands'
parts (three against an exact bfloat16 operand): the matrix unit, not the
vector unit, bounds these kernels. Products take bfloat16 operands where
the model's type is bfloat16 and always accumulate in float32. The kernel
bodies loop (`fori_loop`) and batch over heads instead of unrolling: a
Pallas kernel is traced and lowered at every call site on every set-up. At
widths the kernels are not written for (not whole 128-lane rows) the same
part runs in XLA (`_chunked_xla`); `last_resolution("kda").derived
["chunk_backend"]` says which ran.

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
_EXP_CAP = 80.0


def chunk_backend(width: int, v_width: int, interpret: bool) -> str:
    """Where the part before the scan runs: the kernels `kda_chunk_fwd` /
    `kda_chunk_bwd` ("pallas") at widths of whole 128-lane rows and always
    in interpret mode, else XLA ("xla")."""
    whole = width % 128 == 0 and v_width % 128 == 0
    return "pallas" if interpret or whole else "xla"


def resolve_head_block(n_heads: int, n_chunks: int = 0, width: int = 128,
                       backend: str = "pallas") -> int:
    """Heads a grid step of the kernels walks together (their products are
    independent, so the scheduler overlaps them). Static a compiled
    program: recorded at trace time as `last_resolution("kda")`, the chunk
    and the block shapes under `derived`, with `chunk_backend` (what
    `chunk_backend()` chose for the part before the scan)."""
    from paddle_tpu.tuning.blocks import Resolution, note_derived

    hb = next(b for b in (4, 2, 1) if n_heads % b == 0)
    note_derived(Resolution("kda", {"chunk": CHUNK, "head_block": hb},
                            "default", "heuristic"),
                 grid=(n_heads // hb, n_chunks), state_block=(hb, width, width),
                 chunk_block=(hb, 1, CHUNK, width), chunk_backend=backend,
                 local_block=(hb, 1, LOCAL_CHUNKS * CHUNK, width) if backend == "pallas" else None)
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
# the part every chunk does at once: Pallas kernels over (blocks of heads,
# chunks), what a chunk builds kept in VMEM; the backward written by hand
# ---------------------------------------------------------------------------

# products of a block of heads at once, [H, ...] x [H, ...]: the head is the
# batch, each head's matrices are contracted as named
_NN, _NT, _TN = ((2,), (1,)), ((2,), (2,)), ((1,), (1,))
# chunks a block of the chunk kernels holds: 128 rows, the MXU's width; what
# each chunk builds stays its own (block-diagonal masks), the products serve
# both chunks at once
LOCAL_CHUNKS = 2


def _bdot(a, b, dims=_NN):
    """One bfloat16 (or the operands' type) pass a head, float32 out."""
    return jax.lax.dot_general(a, b, (dims, ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _parts(x):
    """x as bfloat16 parts whose sum is x: one part where x is bfloat16
    already (a 0/1 mask, an operand of the model's type), else three, which
    carry float32's 24 bits."""
    if isinstance(x, tuple):
        return x
    if x.dtype == jnp.bfloat16:
        return (x,)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _hi(a, b, dims=_NN):
    """A float32 product at float32's precision, as `Precision.HIGHEST`
    takes it on a TPU: the bfloat16 parts' products whose orders sum to at
    most 2 (six of them for two float32 operands, three against an exact
    bfloat16 one), as ONE product over the parts laid side by side
    along the contracted axis, so that the matrix unit sums them in float32
    and no partial product goes through VMEM. `a` and `b` are arrays or
    their `_parts`, so a caller splits an operand it uses twice once."""
    a, b = _parts(a), _parts(b)
    pairs = [(i, j) for i in range(len(a)) for j in range(len(b)) if i + j <= 2]
    if len(pairs) == 1:
        return _bdot(a[0], b[0], dims)
    (ca,), (cb,) = dims
    return _bdot(jnp.concatenate([a[i] for i, _ in pairs], axis=ca),
                 jnp.concatenate([b[j] for _, j in pairs], axis=cb), dims)


def _running_sum(mask, x):
    """mask [W, W] (0/1, bfloat16) times x [H, W, K], float32: a running sum
    down each chunk's rows (or up them, with the transposed mask). Each
    bfloat16 part of x is summed in a product of its own, and the three
    sums are added smallest first: a part's products with a 0/1 mask are
    exact, their sum nearly so, where the parts side by side in one product
    would round every row's sum at the scale of the larger parts (terms
    that cancel, as the decays' gradients about the middle of a chunk do,
    would keep that rounding)."""
    mask = jnp.broadcast_to(mask, (x.shape[0],) + mask.shape)
    out = None
    for part in reversed(_parts(x)):
        p = _bdot(mask, part)
        out = p if out is None else out + p
    return out


def _masks(w: int):
    """The 0/1 masks of a block of W = LOCAL_CHUNKS chunks, made once in XLA
    (constants) and read by every grid step: the products' in bfloat16
    (exact) [2, W, W]: running sum, its transpose; the elementwise ones in
    float32 [2 + L, W, W]: strictly lower inside a chunk, the diagonal, and
    for each of the L = log2(CHUNK) doubling steps of
    `_unit_lower_inverses` the A21 blocks it joins."""
    row = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    shift = CHUNK.bit_length() - 1
    same = (row >> shift) == (col >> shift)
    steps = [((row >> i) & 1 == 1) & ((col >> i) == (row >> i) - 1) for i in range(shift)]
    mxu = jnp.stack([same & (row >= col), same & (row <= col)]).astype(jnp.bfloat16)
    vpu = jnp.stack([same & (row > col), row == col] + steps).astype(jnp.float32)
    return mxu, vpu


_CUM, _CUM_T = 0, 1                # in the bfloat16 masks
_STRICT, _DIAG, _STEP0 = 0, 1, 2   # in the float32 masks


def _chunk_sums(x, rows: int):
    """Each row of x [H, W, K] replaced by the sum of the first `rows` rows
    of its chunk: exact float32 sums, no product."""
    out = []
    for j in range(x.shape[1] // CHUNK):
        total = jnp.sum(x[:, j * CHUNK: j * CHUNK + rows], axis=1, keepdims=True)
        out.append(jnp.broadcast_to(total, (x.shape[0], CHUNK, x.shape[2])))
    return jnp.concatenate(out, axis=1)


def _first_half(w: int):
    """[W, 1]: whether a row lies in the first half of its chunk."""
    return (jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) & (CHUNK - 1)) < CHUNK // 2


def _tri():
    return (jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1))


def _unit_lower_inverses(a, vpu_ref):
    """(I + A)^-1 for each head's strictly lower, block-diagonal A of
    a [H, W, W], float32. First the inverse of 2s x 2s diagonal blocks from
    that of s x s ones, for s = 1, 2, ..., CHUNK/2: [[T11, 0], [-T22 A21
    T11, T22]], i.e. T <- T - T (A restricted to the A21 blocks) T, products
    of one bfloat16 pass: the triangular solve's recursive form, where each
    step multiplies inverses of diagonal blocks and no power of A is ever
    formed (the Neumann product's powers of A pass 1e30 and cancel to NaN
    on the chip). The first step, from T = I, is I - A restricted to its A21
    blocks, exact; the others are a `fori_loop`: the body is the same for
    any chunk. Then two Newton steps T <- T + T R, R = I - (I + A) T, each
    of which squares the relative error, from about 1e-2 to float32's
    rounding (tests/test_kimi_linear.py). They start from T0, the doubling's
    result rounded to bfloat16, whose products with a float32 operand take
    three passes: R1 = I - T0 - A T0 is exact to float32, T1 = T0 + C1 with
    C1 = T0 R1; R2 = R1 - (I + A) C1, with C1 small, to 16 bits; T2 = T1 +
    T0 R2 + C1 R2."""
    bf, f32 = jnp.bfloat16, jnp.float32
    eye = jnp.broadcast_to(vpu_ref[_DIAG], a.shape)

    def step(i, t):
        tb = t.astype(bf)
        return t - _bdot(tb, _bdot((a * vpu_ref[_STEP0 + i]).astype(bf), tb).astype(bf))

    t0 = jax.lax.fori_loop(1, CHUNK.bit_length() - 1, step,
                           eye - a * vpu_ref[_STEP0]).astype(bf)
    ap = _parts(a)
    r1 = eye - t0.astype(f32) - _hi(ap, t0)
    c1 = _hi(t0, _parts(r1)[:2])
    cp = _parts(c1)
    r2 = r1 - c1 - _hi(ap, cp[:2])
    c2 = _hi(t0, _parts(r2)[:2]) + _bdot(cp[0], r2.astype(bf))
    return t0.astype(f32) + (c1 + c2)


def _chunk_local(q, k, g, brow, vpu_ref, cum):
    """What the chunks of a block of heads build before the scan, float32
    values (`_chunked_xla` writes the same in XLA): q, k [H, W, K] in the
    model's type; g [H, W, K] float32 log-decays; brow [H, 1, W] the betas.
    Every row carries its chunk's middle and last running sum."""
    lo, f32 = q.dtype, jnp.float32
    qf, kf = q.astype(f32), k.astype(f32)
    bcol = jnp.sum(vpu_ref[_DIAG] * brow, axis=2, keepdims=True)    # [H, W, 1]
    gc = _running_sum(cum, g)                              # G_t
    mid = _chunk_sums(g, CHUNK // 2)                       # G at the chunk's middle
    last = _chunk_sums(g, CHUNK)                           # G_C
    up = jnp.exp(jnp.minimum(gc - mid, _EXP_CAP))          # exp(G_t - G_mid)
    down = jnp.exp(jnp.minimum(mid - gc, _EXP_CAP))        # exp(G_mid - G_i)
    eg = jnp.exp(gc)
    ku, kn, qu = (kf * up).astype(lo), (kf * down).astype(lo), (qf * up).astype(lo)
    s = _bdot(ku, kn, _NT)                                 # [H, W, W]
    # a select, not a product: above the diagonal ku kn^T may overflow
    a = jnp.where(vpu_ref[_STRICT] > 0, bcol * s, 0.0)
    return dict(qf=qf, kf=kf, brow=brow, bcol=bcol, gc=gc, mid=mid, last=last, up=up,
                down=down, eg=eg, kg=kf * eg, ku=ku, kn=kn, qu=qu, s=s, a=a)


def _solved(t, brow, v, kg):
    """Ubar | Wbar = T [b v | b exp(G) k] = (T diag(b)) [v | exp(G) k], v
    exact in its own type: float32 products, [H, W, V], [H, W, K]."""
    tb = _parts(t * brow)
    return _hi(tb, v), _hi(tb, kg)


def _chunk_fwd_kernel(mxu_ref, vpu_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                      qd_ref, w_ref, u_ref, pm_ref, kd_ref, gam_ref):
    """A block of heads at once: every value is [H, ...] and every product
    a batch over heads, so the body's size is one head's (the scheduler
    overlaps the heads' independent products)."""
    lo, w = q_ref.dtype, q_ref.shape[2]
    x = _chunk_local(q_ref[:, 0], k_ref[:, 0], g_ref[:, 0], b_ref[:, 0], vpu_ref,
                     mxu_ref[_CUM])
    for j in range(w // CHUNK):
        rows = slice(j * CHUNK, (j + 1) * CHUNK)
        pm = _bdot(x["qu"][:, rows], x["kn"][:, rows], _NT)
        pm_ref[:, 0, j] = jnp.where(_tri(), pm, 0.0).astype(lo)
        gam_ref[:, 0, j:j + 1] = jnp.exp(x["last"][:, j * CHUNK: j * CHUNK + 1])
    qd_ref[:, 0] = (x["qf"] * x["eg"]).astype(lo)
    kd_ref[:, 0] = (x["kf"] * jnp.exp(x["last"] - x["gc"])).astype(lo)
    ubar, wbar = _solved(_unit_lower_inverses(x["a"], vpu_ref), x["brow"], v_ref[:, 0],
                         x["kg"])
    u_ref[:, 0], w_ref[:, 0] = ubar.astype(lo), wbar.astype(lo)


def _chunk_bwd_kernel(mxu_ref, vpu_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                      dqd_ref, dw_ref, du_ref, dpm_ref, dkd_ref, dgam_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, db_ref):
    """The forward of the chunks made again, then their gradient by hand
    (docs/linear_attention.md has the formulas); a block of heads at once,
    as `_chunk_fwd_kernel`."""
    lo, f32 = q_ref.dtype, jnp.float32
    w, vw = q_ref.shape[2], v_ref.shape[-1]
    x = _chunk_local(q_ref[:, 0], k_ref[:, 0], g_ref[:, 0], b_ref[:, 0], vpu_ref,
                     mxu_ref[_CUM])
    t = _unit_lower_inverses(x["a"], vpu_ref)
    qf, kf, gc, eg, up, down, bcol = (x[n] for n in ("qf", "kf", "gc", "eg", "up",
                                                      "down", "bcol"))
    dqd, dkd = dqd_ref[:, 0].astype(f32), dkd_ref[:, 0].astype(f32)
    el = jnp.exp(x["last"] - gc)
    dq, dk = dqd * eg, dkd * el
    dgc = dqd * qf * eg - dkd * kf * el
    dlast = _chunk_sums(dkd * kf * el, CHUNK)               # d G_C, on every row of a chunk
    # P = lower(qu kn^T), and exp(G_C), a chunk at a time
    dqu, dkn, dgam = [], [], []
    for j in range(w // CHUNK):
        rows = slice(j * CHUNK, (j + 1) * CHUNK)
        dp = jnp.where(_tri(), dpm_ref[:, 0, j].astype(f32), 0.0).astype(lo)
        dqu.append(_bdot(dp, x["kn"][:, rows]))
        dkn.append(_bdot(dp, x["qu"][:, rows], _TN))
        gam = jnp.exp(x["last"][:, j * CHUNK: j * CHUNK + 1])
        dgam.append(jnp.broadcast_to(dgam_ref[:, 0, j:j + 1] * gam, gam.shape[:1] + (CHUNK,)
                                     + gam.shape[2:]))
    dqu, dkn = jnp.concatenate(dqu, axis=1), jnp.concatenate(dkn, axis=1)
    # Ubar | Wbar = T rhs = T diag(b) [v | exp(G) k]: d rhs = T^T dX, and
    # dA = -d rhs (T rhs)^T = -((d rhs [v | exp(G) k]^T) diag(b)) T^T, so
    # that Ubar | Wbar are not made again
    v = v_ref[:, 0]
    dx = jnp.concatenate([du_ref[:, 0], dw_ref[:, 0]], axis=-1)
    tp = _parts(t)
    drhs = _hi(tp, dx, _TN)
    drv, drw = drhs[..., :vw], drhs[..., vw:]
    m = (_hi(drv, v, _NT) + _hi(drw, x["kg"], _NT)) * x["brow"]
    da = -vpu_ref[_STRICT] * _hi(m, tp, _NT)
    dv = bcol * drv
    dk = dk + bcol * drw * eg
    dgc = dgc + bcol * drw * x["kg"]
    db = (jnp.sum(jnp.where(vpu_ref[_STRICT] > 0, da * x["s"], 0.0), axis=2, keepdims=True)
          + jnp.sum(drv * v.astype(f32) + drw * x["kg"], axis=2, keepdims=True))
    # A = strict lower(b ku kn^T)
    ds = (bcol * da).astype(lo)
    dku = _bdot(ds, x["kn"])
    dkn = dkn + _bdot(ds, x["ku"], _TN)
    # the decayed copies, about the middle: exp(min(G - G_mid, cap))
    dk = dk + dku * up + dkn * down
    dq = dq + dqu * up
    d_up = jnp.where(gc - x["mid"] < _EXP_CAP, (dku * kf + dqu * qf) * up, 0.0)
    d_down = jnp.where(x["mid"] - gc < _EXP_CAP, dkn * kf * down, 0.0)
    dq_ref[:, 0] = dq.astype(dq_ref.dtype)
    dk_ref[:, 0] = dk.astype(dk_ref.dtype)
    dv_ref[:, 0] = dv.astype(dv_ref.dtype)
    # the transposes of the running sum, of the sums to the middle and of
    # the whole chunk
    dg_ref[:, 0] = (_running_sum(mxu_ref[_CUM_T], dgc + d_up - d_down)
                    + jnp.where(_first_half(w), _chunk_sums(d_down - d_up, CHUNK), 0.0)
                    + dlast + jnp.concatenate(dgam, axis=1))
    db_ref[:, 0] = jnp.sum(vpu_ref[_DIAG] * db, axis=1, keepdims=True)


def _mask_specs(w):
    mxu, vpu = _masks(w)
    const = lambda x: pl.BlockSpec(x.shape, lambda b, c: (0, 0, 0))   # noqa: E731
    return (mxu, vpu), [const(mxu), const(vpu)]


def _local_fwd(q, k, v, g, brow, hb, interpret):
    """q, k, g [BH, NB, W, K], v [BH, NB, W, V], brow [BH, NB, 1, W] (NB
    blocks of W = LOCAL_CHUNKS chunks) -> qd, Wbar [BH, NB, W, K], Ubar
    [BH, NB, W, V], P [BH, NB, LOCAL_CHUNKS, C, C], exp(G_C - G) k
    [BH, NB, W, K] (model's type), exp(G_C) [BH, NB, LOCAL_CHUNKS, K]."""
    bh, nb, w, kd = q.shape
    v_w, n, c = v.shape[-1], w // CHUNK, CHUNK
    lo = q.dtype
    masks, mask_specs = _mask_specs(w)
    with _compat.kernel_trace_ctx(interpret):
        return pl.pallas_call(
            _chunk_fwd_kernel,
            grid=(bh // hb, nb),
            in_specs=mask_specs + [_spec(hb, w, kd), _spec(hb, w, kd), _spec(hb, w, v_w),
                                   _spec(hb, w, kd), _spec(hb, 1, w)],
            out_specs=[_spec(hb, w, kd), _spec(hb, w, kd), _spec(hb, w, v_w),
                       _spec(hb, n, c, c), _spec(hb, w, kd), _spec(hb, n, kd)],
            out_shape=[jax.ShapeDtypeStruct((bh, nb, w, kd), lo),
                       jax.ShapeDtypeStruct((bh, nb, w, kd), lo),
                       jax.ShapeDtypeStruct((bh, nb, w, v_w), lo),
                       jax.ShapeDtypeStruct((bh, nb, n, c, c), lo),
                       jax.ShapeDtypeStruct((bh, nb, w, kd), lo),
                       jax.ShapeDtypeStruct((bh, nb, n, kd), jnp.float32)],
            interpret=interpret,
            **_compat.kernel_name("kda_chunk_fwd"),
        )(*masks, q, k, v, g, brow)


def _local_bwd(q, k, v, g, brow, dqd, dw, du, dpm, dkd, dgam, hb, interpret):
    bh, nb, w, kd = q.shape
    v_w, n, c = v.shape[-1], w // CHUNK, CHUNK
    masks, mask_specs = _mask_specs(w)
    with _compat.kernel_trace_ctx(interpret):
        return pl.pallas_call(
            _chunk_bwd_kernel,
            grid=(bh // hb, nb),
            in_specs=mask_specs + [_spec(hb, w, kd), _spec(hb, w, kd), _spec(hb, w, v_w),
                                   _spec(hb, w, kd), _spec(hb, 1, w),
                                   _spec(hb, w, kd), _spec(hb, w, kd), _spec(hb, w, v_w),
                                   _spec(hb, n, c, c), _spec(hb, w, kd), _spec(hb, n, kd)],
            out_specs=[_spec(hb, w, kd), _spec(hb, w, kd), _spec(hb, w, v_w),
                       _spec(hb, w, kd), _spec(hb, 1, w)],
            out_shape=[jax.ShapeDtypeStruct((bh, nb, w, kd), q.dtype),
                       jax.ShapeDtypeStruct((bh, nb, w, kd), k.dtype),
                       jax.ShapeDtypeStruct((bh, nb, w, v_w), v.dtype),
                       jax.ShapeDtypeStruct((bh, nb, w, kd), jnp.float32),
                       jax.ShapeDtypeStruct((bh, nb, 1, w), jnp.float32)],
            interpret=interpret,
            **_compat.kernel_name("kda_chunk_bwd"),
        )(*masks, q, k, v, g, brow, dqd, dw, du, dpm, dkd, dgam)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _local(q, k, v, g, brow, hb, interpret):
    return _local_fwd(q, k, v, g, brow, hb, interpret)


def _local_vjp_fwd(q, k, v, g, brow, hb, interpret):
    # nothing of the chunks is kept: the backward kernel makes it again
    return _local_fwd(q, k, v, g, brow, hb, interpret), (q, k, v, g, brow)


def _local_vjp_bwd(hb, interpret, res, cts):
    dqd, dw, du, dpm, dkd, dgam = cts
    return tuple(_local_bwd(*res, dqd, dw, du, dpm, dkd, dgam.astype(jnp.float32),
                            hb, interpret))


_local.defvjp(_local_vjp_fwd, _local_vjp_bwd)


def _chunked_pallas(q, k, v, g, beta, hb, interpret):
    """`_chunked_xla`'s arguments and result, T a multiple of
    LOCAL_CHUNKS * CHUNK; the part before the scan is the kernels
    `kda_chunk_fwd` / `kda_chunk_bwd`."""
    bh, t, _ = q.shape
    c, nc, w = CHUNK, t // CHUNK, LOCAL_CHUNKS * CHUNK
    blk = lambda x: x.reshape(bh, t // w, w, *x.shape[2:])    # noqa: E731
    qd, wbar, ubar, pm, kd, gam = _local(
        blk(q), blk(k), blk(v), blk(g.astype(jnp.float32)),
        beta.astype(jnp.float32).reshape(bh, t // w, 1, w), hb, interpret)
    ch = lambda x: x.reshape(bh, nc, c, -1)                   # noqa: E731
    o = _scan(ch(qd), ch(wbar), ch(ubar), ch(pm), jnp.swapaxes(ch(kd), -1, -2),
              gam.reshape(bh, nc, -1, 1), hb, interpret)
    return o.reshape(bh, t, -1)


# ---------------------------------------------------------------------------
# the same part in plain XLA, differentiated by JAX: for widths the kernels
# are not written for
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


def _chunked_xla(q, k, v, g, beta, hb, interpret):
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
    backend = chunk_backend(kd, v.shape[-1], interpret)
    pad = (-t) % (CHUNK * (LOCAL_CHUNKS if backend == "pallas" else 1))
    hb = resolve_head_block(b * h, (t + pad) // CHUNK, kd, backend)

    def heads_first(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, t, *x.shape[3:])
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) if pad else x

    # a block of heads at a time, one after the other: what the chunks of
    # ALL heads hold at once (a dozen [tokens, heads * K] float32 arrays and
    # the states) is several GB at 16k tokens; a block's is a sixteenth
    chunked = _chunked_pallas if backend == "pallas" else _chunked_xla
    run = jax.checkpoint(functools.partial(chunked, hb=hb, interpret=interpret))
    blocks = [heads_first(x) for x in (q, k, v, g, beta)]
    blocks = [x.reshape(b * h // hb, hb, *x.shape[1:]) for x in blocks]
    o = jax.lax.map(lambda xs: run(*xs), tuple(blocks))
    o = o.reshape(b * h, t + pad, -1)[:, :t].reshape(b, h, t, -1)
    return checkpoint_name(jnp.moveaxis(o, 1, 2).astype(v.dtype), KDA_OUT)
