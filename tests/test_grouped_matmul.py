"""Pallas grouped/ragged matmul (the dropless-MoE compute primitive).

Parity is asserted against a dense one-hot-masked reference for BOTH
backends — the Pallas kernels under interpret mode (the exact kernel code
the TPU runs, incl. the shared `_seg_blocks_can_touch` block-skip
predicate) and the XLA block-gather fallback — in fp32 (<=1e-5) and bf16
(<=1e-3), forward and dx/dw. The visit-count kernel must agree with the
predicate evaluated independently in numpy.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import force_interpret
from paddle_tpu.ops.pallas.grouped_matmul import (
    col_tiles, expected_visit_counts, grouped_matmul,
    grouped_matmul_visit_counts, pick_block_rows,
)

gm = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")


def _dense_ref(x, w, gids):
    """y[i] = x[i] @ w[gids[i]] via the dense one-hot mask (gids == G maps
    to the all-zero one-hot row, i.e. padding rows yield zeros)."""
    G = w.shape[0]
    oh = jax.nn.one_hot(gids, G, dtype=jnp.float32)
    return jnp.einsum("mg,md,gdh->mh", oh, x.astype(jnp.float32),
                      w.astype(jnp.float32))


def _aligned_gids(rs, n_blocks, bm, G, trash_blocks=1):
    """Block-aligned grouped layout (the dispatcher's contract): each
    bm-row block belongs to one group; the last blocks are padding."""
    blk = np.sort(rs.randint(0, G, n_blocks - trash_blocks))
    blk = np.concatenate([blk, np.full(trash_blocks, G)])
    return np.repeat(blk, bm).astype(np.int32)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
class TestForwardParity:
    def _run(self, backend, fn):
        if backend == "pallas":
            with force_interpret():
                return fn()
        return fn()

    def test_fp32_matches_dense_masked(self, backend):
        rs = np.random.RandomState(0)
        bm, G = 8, 4
        gids = _aligned_gids(rs, 12, bm, G)
        x = rs.randn(gids.size, 16).astype(np.float32)
        w = rs.randn(G, 16, 24).astype(np.float32)
        y = self._run(backend, lambda: grouped_matmul(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(gids),
            block_rows=bm, backend=backend))
        yr = _dense_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gids))
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_matches_dense_masked(self, backend):
        rs = np.random.RandomState(1)
        bm, G = 8, 4
        gids = _aligned_gids(rs, 8, bm, G)
        x = jnp.asarray(rs.randn(gids.size, 16), jnp.bfloat16)
        w = jnp.asarray(rs.randn(G, 16, 24) * 0.25, jnp.bfloat16)
        y = self._run(backend, lambda: grouped_matmul(
            x, w, jnp.asarray(gids), block_rows=bm, backend=backend))
        yr = _dense_ref(x, w, jnp.asarray(gids))
        assert y.dtype == jnp.float32  # fp32 accumulation contract
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-2, atol=1e-3)

    def test_padding_rows_stay_zero(self, backend):
        rs = np.random.RandomState(2)
        bm, G = 8, 3
        gids = _aligned_gids(rs, 6, bm, G, trash_blocks=2)
        x = rs.randn(gids.size, 8).astype(np.float32)
        w = rs.randn(G, 8, 8).astype(np.float32)
        y = self._run(backend, lambda: grouped_matmul(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(gids),
            block_rows=bm, backend=backend))
        np.testing.assert_array_equal(np.asarray(y)[gids == G], 0.0)

    def test_grads_dx_dw_parity(self, backend):
        rs = np.random.RandomState(3)
        bm, G = 8, 4
        gids = _aligned_gids(rs, 10, bm, G)
        x = jnp.asarray(rs.randn(gids.size, 12), jnp.float32)
        w = jnp.asarray(rs.randn(G, 12, 20), jnp.float32)

        def loss(fn):
            return lambda xv, wv: jnp.sum(
                jnp.sin(fn(xv, wv, jnp.asarray(gids))))

        gmm = loss(lambda xv, wv, g: grouped_matmul(
            xv, wv, g, block_rows=bm, backend=backend))
        ref = loss(_dense_ref)
        dx, dw = self._run(backend, lambda: jax.grad(gmm, (0, 1))(x, w))
        dxr, dwr = jax.grad(ref, (0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                                   rtol=1e-5, atol=1e-5)


class TestPallasGeneralLayouts:
    def test_unaligned_grouped_layout(self):
        """The Pallas kernel masks WITHIN blocks, so any group-sorted
        layout (bucket boundaries mid-block) is exact — only the xla
        fallback requires block alignment."""
        rs = np.random.RandomState(4)
        bm, G = 8, 4
        gids = np.sort(rs.randint(0, G + 1, 64)).astype(np.int32)
        x = rs.randn(64, 8).astype(np.float32)
        w = rs.randn(G, 8, 8).astype(np.float32)
        with force_interpret():
            y = grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(gids), block_rows=bm,
                               backend="pallas")
        yr = _dense_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gids))
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    def test_rejects_bad_rows(self):
        with pytest.raises(ValueError, match="multiple of block_rows"):
            grouped_matmul(jnp.zeros((12, 4)), jnp.zeros((2, 4, 4)),
                           jnp.zeros((12,), jnp.int32), block_rows=8)

    def test_rejects_bad_backend(self):
        with pytest.raises(ValueError, match="moe_gmm_backend"):
            grouped_matmul(jnp.zeros((8, 4)), jnp.zeros((2, 4, 4)),
                           jnp.zeros((8,), jnp.int32), block_rows=8,
                           backend="cuda")


class TestVisitCounts:
    def test_kernel_matches_predicate(self):
        rs = np.random.RandomState(5)
        bm, G = 8, 6
        gids = np.sort(rs.randint(0, G + 1, 128)).astype(np.int32)
        vc = np.asarray(grouped_matmul_visit_counts(gids, G, bm,
                                                    interpret=True))
        np.testing.assert_array_equal(vc, expected_visit_counts(gids, G, bm))

    def test_aligned_layout_visits_one_group_per_real_block(self):
        rs = np.random.RandomState(6)
        bm, G = 8, 4
        gids = _aligned_gids(rs, 10, bm, G, trash_blocks=2)
        vc = np.asarray(grouped_matmul_visit_counts(gids, G, bm,
                                                    interpret=True))
        blk = gids.reshape(-1, bm)[:, 0]
        np.testing.assert_array_equal(vc, (blk < G).astype(np.int32))
        # the sparsity the layer sees: visited / (blocks * G)
        assert vc.sum() == (blk < G).sum() < vc.size * G

    def test_pick_block_rows(self):
        assert pick_block_rows(128 * 64, 8) == 128
        assert pick_block_rows(8 * 40, 8) == 32
        assert pick_block_rows(64, 8) == 8


class TestColumnTiles:
    """The column tile is cut by what VMEM holds, not by what divides the
    width: 11 x 128 and 13 x 128 have no 128-multiple divisor but 128 and
    themselves, so the rule before PR 38 gave them 128-column tiles."""

    @pytest.mark.parametrize("n", [11 * 128, 13 * 128])
    @pytest.mark.parametrize("cut", ["partial", "whole"])
    def test_partial_or_whole_tile_matches_dense(self, n, cut, monkeypatch):
        rs = np.random.RandomState(7)
        bm, G, k = 8, 3, 16
        fwd = gm._fwd_sizes(bm, jnp.float32, jnp.float32)
        dws = gm._dw_sizes(k, jnp.float32, jnp.float32)
        if cut == "partial":    # the budget of a 512-column tile at this k
            budget = max(gm._working_set(bm, k, 512, *fwd), gm._working_set(bm, k, 512, *dws))
            monkeypatch.setattr(gm._compat, "vmem_budget", lambda: budget)
        bn = gm._col_tile(bm, k, n, *fwd)
        assert bn == gm._col_tile(bm, k, n, *dws)      # dw cut alike
        assert (bn == n) == (cut == "whole") and (cut == "whole" or n % bn)
        gids = _aligned_gids(rs, 6, bm, G, trash_blocks=2)
        x = jnp.asarray(rs.randn(gids.size, k), jnp.float32)
        w = jnp.asarray(rs.randn(G, k, n), jnp.float32)
        g = jnp.asarray(gids)

        def loss(fn):
            return lambda xv, wv: jnp.sum(jnp.sin(fn(xv, wv, g)))

        gmm = lambda xv, wv, g: grouped_matmul(        # noqa: E731
            xv, wv, g, block_rows=bm, backend="pallas", aligned=True)
        with force_interpret():
            y = gmm(x, w, g)
            dx, dw = jax.grad(loss(gmm), (0, 1))(x, w)
        dxr, dwr = jax.grad(loss(_dense_ref), (0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_ref(x, w, g)),
                                   rtol=1e-5, atol=1e-5)
        # dx sums n products of size ~10 in another order than the reference
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr), rtol=1e-5, atol=5e-4)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr), rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(y)[gids == G], 0.0)
        np.testing.assert_array_equal(np.asarray(dx)[gids == G], 0.0)

    @pytest.mark.parametrize("bm,k,n,w_dtype", [
        (128, 2048, 1408, jnp.bfloat16), (128, 1408, 2048, jnp.bfloat16),
        (128, 7168, 2048, jnp.bfloat16), (128, 7168, 1408, jnp.float32),
        (8, 16, 1408, jnp.float32), (128, 4096, 24, jnp.bfloat16),
        (32, 20480, 11 * 128, jnp.bfloat16),
    ])
    def test_rule_takes_the_fewest_tiles_that_fit(self, bm, k, n, w_dtype):
        budget = gm._compat.vmem_budget()
        for sizes in (gm._fwd_sizes(bm, jnp.bfloat16, w_dtype),
                      gm._dw_sizes(k, jnp.bfloat16, jnp.bfloat16)):
            bn = gm._col_tile(bm, k, n, *sizes)
            tiles = -(-n // bn)
            assert bn == n or bn % 128 == 0
            assert (tiles - 1) * bn < n <= tiles * bn          # they cover n
            ws = lambda b: gm._working_set(bm, k, b, *sizes)  # noqa: E731
            assert ws(bn) <= budget or bn == 128
            if tiles > 1:       # one tile fewer does not fit
                fewer = n if tiles == 2 else gm._compat.lanes(-(-n // (tiles - 1)))
                assert ws(fewer) > budget

    def test_tiles_follow_the_operands_dtypes(self):
        """A v5e's budget is half its 128 MiB, and where no TPU answers it is
        a v5e's. float32 weights double the forward's weight tile: a
        DeepSeek-V3-wide product whose bfloat16 tile fits whole takes two."""
        assert gm._compat.vmem_budget() == 64 * 2**20
        bf = col_tiles(128, 7168, 2048, jnp.bfloat16, jnp.bfloat16)
        f32 = col_tiles(128, 7168, 2048, jnp.bfloat16, jnp.float32)
        assert bf["fwd"] == 1 and f32["fwd"] == 2 and bf["dw"] == f32["dw"]

    @pytest.mark.parametrize("cell,d,h", [
        ("moonlight", 2048, 1408), ("lfm2", 2048, 1536), ("kimi", 2304, 1024)])
    def test_expert_cells_take_one_tile(self, cell, d, h):
        """The counts PERF.md records for the three expert cells (PR 38):
        one tile for every product, forward, dx and dw, at block rows 128."""
        one = {"fwd": 1, "dx": 1, "dw": 1}
        assert col_tiles(128, d, h, jnp.bfloat16, jnp.bfloat16) == one
        assert col_tiles(128, h, d, jnp.bfloat16, jnp.bfloat16) == one
