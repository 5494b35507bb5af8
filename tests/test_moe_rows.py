"""The two row movers of the held experts' layout (`ops/pallas/moe_rows.py`).

The kernels, run under interpretation (the code the TPU runs), against their
`xla` form and against the plain take / `.at[].set` / `.at[].add` the layer
used before them: values and every gradient (source rows, products,
weights), in both types, at both configurations' widths and `k`, over
layouts with an expert no pair chose, every pair on one expert, pairs past
the rows laid out, and no pair here at all. The visit-count twins count what
the kernels fetch: nothing in a block past the live count.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.distributed.models.moe.dropless import ragged_layout
from paddle_tpu.incubate.distributed.models.moe.held_experts import _rows_layout
from paddle_tpu.ops.pallas.flash_attention import force_interpret
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.ops.pallas.moe_rows import (
    expected_combine_visits, expected_gather_visits, rows_backend, rows_combine,
    rows_combine_visit_counts, rows_gather, rows_gather_visit_counts, token_block)

G, BM = 4, 16          # held experts, rows of a block


def _gids(kind, rs, n, k):
    """Group ids of n * k pairs (G: not here) and the rows laid out."""
    nk = n * k
    if kind == "none_here":
        return np.full(nk, G, np.int32), nk // 4
    if kind == "one_expert":                   # every pair on expert 2
        return np.full(nk, 2, np.int32), nk
    gids = np.where(rs.rand(nk) < 0.3, rs.randint(0, G, nk), G).astype(np.int32)
    if kind == "expert_unchosen":
        gids[gids == 1] = G
    rows = int((gids < G).sum())
    return gids, (rows // 2 if kind == "past_rows" else max(rows, BM))


KINDS = ("random", "expert_unchosen", "one_expert", "past_rows", "none_here")


def _case(kind, dtype, d, k, seed=0):
    rs = np.random.RandomState(seed)
    n = 2 * token_block(k)
    gids, rows = _gids(kind, rs, n, k)
    layout = _rows_layout(jnp.asarray(gids), G, k, BM, rows)[:2]
    m = layout[1].shape[0]
    src = jnp.asarray(rs.randn(n, d), dtype)
    y = jnp.asarray(rs.randn(m, d), dtype)
    w = jnp.asarray(rs.rand(n, k) + 0.5, jnp.float32)
    return gids, rows, layout, src, y, w


def _plain(gids, rows, k, src, y, w):
    """The layer's former row traffic: gather-then-scatter into the buffer,
    gather-then-scatter-add out of it, over every row laid out."""
    order, _, dest, gbuf, _ = ragged_layout(jnp.asarray(gids), G, BM, rows=rows)
    here = jnp.take(jnp.asarray(gids), order) < G
    tok = (order // k).astype(jnp.int32)
    wgt = jnp.take(w.reshape(-1), order) * here
    buf = jnp.zeros((gbuf.shape[0], src.shape[1]), src.dtype).at[dest].set(
        jnp.take(src, tok, axis=0), mode="drop")
    yk = jnp.take(y, dest, axis=0, mode="fill", fill_value=0.0).astype(jnp.float32)
    out = jnp.zeros(src.shape, jnp.float32).at[tok].add(yk * wgt[:, None])
    return buf, out.astype(y.dtype)


def _both(src, y, w, layout, held_rows, backend):
    buf = rows_gather(src, layout[0], block_rows=BM, backend=backend)
    out = rows_combine(y, w, layout[0], block_rows=BM, backend=backend)
    # rows past the live blocks are the kernel's to leave unwritten
    return jnp.where(held_rows[:, None], buf, jnp.zeros_like(buf)), out


def _loss(fn):
    def loss(src, y, w):
        buf, out = fn(src, y, w)
        return (jnp.sum(jnp.sin(buf.astype(jnp.float32)))
                + jnp.sum(jnp.cos(out.astype(jnp.float32))), (buf, out))
    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)


def _close(a, b, tol, what):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all() and np.isfinite(b).all(), what
    gap = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6) if a.size else 0.0
    assert gap <= tol, (what, gap)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("dtype,d,k", [("float32", 2048, 4), ("bfloat16", 2048, 4),
                                       ("float32", 2304, 8), ("bfloat16", 2304, 8)])
def test_movers_match_the_xla_form_and_the_plain_traffic(kind, dtype, d, k):
    gids, rows, layout, src, y, w = _case(kind, jnp.dtype(dtype), d, k)
    assert rows_backend("pallas", src.shape[0], d, k, src.dtype) == "pallas"
    gbuf, live = layout[1], int(layout[0].live_blocks)
    held_rows = jnp.arange(gbuf.shape[0]) < live * BM
    assert bool((gbuf[live * BM:] == G).all())       # what the grouped kernels skip
    with force_interpret():
        (l1, (buf1, out1)), g1 = _loss(
            lambda *a: _both(*a, layout, held_rows, "pallas"))(src, y, w)
    (l0, (buf0, out0)), g0 = _loss(
        lambda *a: _both(*a, layout, held_rows, "xla"))(src, y, w)

    def plain(src, y, w):
        buf, out = _plain(gids, rows, k, src, y, w)
        return jnp.where((gbuf < G)[:, None], buf, jnp.zeros_like(buf)), out
    (lp, (bufp, outp)), gp = _loss(plain)(src, y, w)

    tol = 1e-5 if dtype == "float32" else 1.6e-2
    assert np.array_equal(np.asarray(buf1, np.float32), np.asarray(buf0, np.float32))
    assert np.array_equal(np.asarray(buf1, np.float32), np.asarray(bufp, np.float32))
    _close(out1, out0, tol, "combine, kernel against xla")
    _close(out1, outp, tol, "combine, kernel against scatter-add")
    for name, a, b, c in zip(("d_src", "d_y", "d_w"), g1, g0, gp):
        if name == "d_y":           # the products' cotangent, where it is read
            a, b, c = (v[:live * BM] for v in (a, b, c))
        _close(a, b, tol, name + ", kernel against xla")
        _close(a, c, tol, name + ", kernel against the plain traffic")
    # pairs past the rows laid out add nothing, and the layout says how many
    routed = int((gids < G).sum())
    kept = int((np.asarray(layout[0].pair_row) < gbuf.shape[0]).sum())
    assert kept == min(routed, rows) and kept == int((np.asarray(layout[0].row_pair) < gids.size).sum())


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", [4, 8])
def test_visit_counts_follow_the_pairs_not_the_rows_laid_out(kind, k):
    gids, rows, (layout, gbuf), *_ = _case(kind, jnp.float32, 256, k, seed=1)
    m, live = gbuf.shape[0], int(layout.live_blocks)
    kept = min(int((gids < G).sum()), rows)
    with force_interpret():
        got_g = np.asarray(rows_gather_visit_counts(layout, BM))
        got_c = np.asarray(rows_combine_visit_counts(layout))
    assert np.array_equal(got_g, expected_gather_visits(layout, BM))
    assert np.array_equal(got_c, expected_combine_visits(layout))
    assert got_g.sum() == kept and got_c.sum() == kept       # one fetch a pair
    assert not got_g[live:].any()                            # nothing past the live count
    assert live <= -(-kept // BM) + G and (live == 0) == (kept == 0)
    assert got_g.size == m // BM and got_c.size == gids.size // k // token_block(k)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_products_ignore_what_lies_past_the_live_blocks(dtype):
    """`moe_rows_gather` leaves the blocks past the live count unwritten:
    the grouped kernels compute on no block whose ids are past the groups,
    so forward, dx and dw are bit-equal whatever those rows hold."""
    rs = np.random.RandomState(0)
    gids, rows, layout, src, _, _ = _case("random", jnp.dtype(dtype), 256, 4)
    gbuf, live = layout[1], int(layout[0].live_blocks)
    w = jnp.asarray(rs.randn(G, 256, 128) * 0.1, src.dtype)
    with force_interpret():
        buf = rows_gather(src.astype(jnp.float32), layout[0], block_rows=BM)
        assert not bool(jnp.isfinite(buf[live * BM:]).any())   # interpretation's unwritten rows are NaN
        buf = buf.astype(src.dtype)
        clean = jnp.where((jnp.arange(gbuf.shape[0]) < live * BM)[:, None], buf, jnp.zeros_like(buf))

        def run(b):
            fn = lambda b, w: jnp.sum(jnp.sin(grouped_matmul(   # noqa: E731
                b, w, gbuf, block_rows=BM, aligned=True)))
            return jax.value_and_grad(fn, argnums=(0, 1))(b, w)
        (l1, (dx1, dw1)), (l0, (dx0, dw0)) = run(buf), run(clean)
    assert float(l1) == float(l0)
    assert np.array_equal(np.asarray(dw1, np.float32), np.asarray(dw0, np.float32))
    assert np.array_equal(np.asarray(dx1, np.float32), np.asarray(dx0, np.float32))


@pytest.mark.parametrize("n,d,k,dtype,want", [
    (64, 2048, 4, "bfloat16", "pallas"), (64, 2304, 8, "float32", "pallas"),
    (64, 64, 4, "float32", "xla"),        # rows of less than whole lanes
    (40, 2048, 4, "float32", "xla"),      # tokens in no whole blocks
    (64, 2048, 4, "float16", "xla")])
def test_backend_rule(n, d, k, dtype, want):
    assert rows_backend("pallas", n, d, k, jnp.dtype(dtype)) == want
    assert rows_backend("xla", n, d, k, jnp.dtype(dtype)) == "xla"
    with force_interpret():
        assert rows_backend(None, n, d, k, jnp.dtype(dtype)) == want
    assert rows_backend(None, n, d, k, jnp.dtype(dtype)) == "xla"     # no TPU here


def _eqn_counts(jaxpr, counts):
    from jax._src import core

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        counts[name] = counts.get(name, 0) + 1
        for sub in core.jaxprs_in_params(eqn.params):
            _eqn_counts(sub, counts)
    return counts


@pytest.mark.parametrize("num_shared", [0, 1])
def test_a_recomputed_layer_routes_and_lays_out_once(num_shared):
    """`HeldExpertsMoE(recompute=True)`: the gradient's program holds the
    routing and the layout's index work ONCE (they are outside what is run
    again), the gather three times (forward, recomputed forward, the combine's
    backward), the combine twice (forward and the gather's backward: its
    output is no input of the backward, so it is not run again) and the
    grouped products as before."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe.held_experts import HeldExpertsMoE
    from paddle_tpu.parallel import functional_call

    def counts(recompute):
        paddle.seed(0)
        layer = HeldExpertsMoE(256, 16, 128, 4, held_experts=(4, 8), block_rows=16,
                               num_shared=num_shared, recompute=recompute)
        names, params = zip(*layer.named_parameters())
        leaves = [jnp.asarray(p.numpy()) for p in params]
        x = jnp.asarray(np.random.RandomState(0).randn(64, 256), jnp.float32)

        def loss(x, *w):
            return jnp.sum(jnp.sin(functional_call(layer, w, (x,))._value))
        with force_interpret():
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(len(leaves) + 1))))(x, *leaves)
        return _eqn_counts(jaxpr.jaxpr, {})

    once, kept = counts(True), counts(False)
    for name in ("top_k", "cumsum"):
        assert once[name] == kept[name] > 0, (name, once[name], kept[name])
    assert (once["moe_rows_gather"], once["moe_rows_combine"]) == (3, 2)
    assert kept["moe_rows_gather"] == kept["moe_rows_combine"] == 2
    # three products a pass, their dx and dw: 9 + 6 with the forward run again
    assert (once["grouped_matmul"], once["grouped_matmul_dw"]) == (9, 3)
    assert (kept["grouped_matmul"], kept["grouped_matmul_dw"]) == (6, 3)
