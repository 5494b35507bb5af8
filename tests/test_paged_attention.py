"""Paged decode-attention kernel: interpret-mode parity vs the XLA
reference and vs dense per-request attention (incl. GQA and bf16), the
null-page/inactive-row contracts, and the page-visit counter's
O(sum active tokens) proof."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.paged_attention import (
    page_visit_counts, paged_attention, paged_attention_reference,
    paged_decode_attention)


def _build_case(rng, batch, hq, hkv, d, ps, pool_pages, pages_per_seq,
                lens, dtype=np.float32):
    """Random pools + a non-overlapping page chain per active sequence."""
    q = rng.randn(batch, hq, d).astype(dtype)
    kp = rng.randn(hkv, pool_pages, ps, d).astype(dtype)
    vp = rng.randn(hkv, pool_pages, ps, d).astype(dtype)
    pt = np.zeros((batch, pages_per_seq), np.int32)
    nxt = 1                                   # page 0 = reserved null page
    for b, ln in enumerate(lens):
        need = -(-ln // ps)
        pt[b, :need] = np.arange(nxt, nxt + need)
        nxt += need
    assert nxt <= pool_pages
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(np.asarray(lens, np.int32)))


def _dense_ref(q, kp, vp, pt, lens):
    """Per-request dense softmax over the gathered context (numpy)."""
    q, kp, vp, pt = (np.asarray(q, np.float32), np.asarray(kp, np.float32),
                     np.asarray(vp, np.float32), np.asarray(pt))
    b, hq, d = q.shape
    hkv, _, ps, _ = kp.shape
    g = hq // hkv
    out = np.zeros((b, hq, d), np.float32)
    for i in range(b):
        ln = int(lens[i])
        if ln == 0:
            continue
        pos = np.arange(ln)
        k = kp[:, pt[i, pos // ps], pos % ps]          # [Hkv, ln, D]
        v = vp[:, pt[i, pos // ps], pos % ps]
        qi = q[i].reshape(hkv, g, d) / math.sqrt(d)
        s = np.einsum("hgd,hsd->hgs", qi, k)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hgs,hsd->hgd", p, v).reshape(hq, d)
    return out


class TestKernelParity:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
    def test_fp32_parity_vs_reference_and_dense(self, paged_interpret,
                                                hq, hkv):
        rng = np.random.RandomState(0)
        lens = [7, 0, 22, 13]                     # ragged + inactive row
        q, kp, vp, pt, ln = _build_case(rng, 4, hq, hkv, 16, 4, 32, 6, lens)
        out = paged_decode_attention(q, kp, vp, pt, ln)
        ref = paged_attention_reference(q, kp, vp, pt, ln)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        dense = _dense_ref(q, kp, vp, pt, ln)
        np.testing.assert_allclose(np.asarray(out), dense,
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_parity_gqa(self, paged_interpret):
        rng = np.random.RandomState(1)
        lens = [9, 31, 4, 16]
        q, kp, vp, pt, ln = _build_case(rng, 4, 8, 2, 32, 8, 24, 4, lens)
        qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
        out = paged_decode_attention(qb, kb, vb, pt, ln)
        ref = paged_attention_reference(qb, kb, vb, pt, ln)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=1e-3, rtol=1e-2)

    def test_inactive_row_outputs_zero(self, paged_interpret):
        rng = np.random.RandomState(2)
        q, kp, vp, pt, ln = _build_case(rng, 3, 4, 4, 8, 4, 16, 4,
                                        [5, 0, 3])
        out = np.asarray(paged_decode_attention(q, kp, vp, pt, ln))
        assert np.all(out[1] == 0)
        assert np.all(np.isfinite(out))

    def test_null_page_contents_never_leak(self, paged_interpret):
        """Dead page-table slots DMA the null page; poisoning it must not
        change any output (compute on skipped pages is masked)."""
        rng = np.random.RandomState(3)
        q, kp, vp, pt, ln = _build_case(rng, 2, 4, 2, 8, 4, 16, 6, [6, 10])
        out0 = np.asarray(paged_decode_attention(q, kp, vp, pt, ln))
        kp2 = kp.at[:, 0].set(1e4)
        vp2 = vp.at[:, 0].set(-1e4)
        out1 = np.asarray(paged_decode_attention(q, kp2, vp2, pt, ln))
        np.testing.assert_array_equal(out0, out1)

    def test_partial_last_page_masked(self, paged_interpret):
        """Positions past context_lens inside the last page carry garbage;
        poisoning them must not change the output."""
        rng = np.random.RandomState(4)
        q, kp, vp, pt, ln = _build_case(rng, 1, 4, 4, 8, 8, 8, 2, [5])
        last = int(np.asarray(pt)[0, 0])
        kp2 = kp.at[:, last, 5:].set(1e4)
        vp2 = vp.at[:, last, 5:].set(-1e4)
        out0 = np.asarray(paged_decode_attention(q, kp, vp, pt, ln))
        out1 = np.asarray(paged_decode_attention(q, kp2, vp2, pt, ln))
        np.testing.assert_array_equal(out0, out1)

    def test_dispatcher_routes_to_kernel_under_fixture(self, paged_interpret,
                                                       monkeypatch):
        import paddle_tpu.ops.pallas.paged_attention as mod

        called = {}
        real = mod.paged_decode_attention

        def spy(*a, **kw):
            called["kernel"] = True
            return real(*a, **kw)

        monkeypatch.setattr(mod, "paged_decode_attention", spy)
        rng = np.random.RandomState(5)
        q, kp, vp, pt, ln = _build_case(rng, 2, 4, 4, 8, 4, 8, 2, [3, 6])
        paged_attention(q, kp, vp, pt, ln)
        assert called.get("kernel")

    def test_dispatcher_falls_back_to_xla_off_tpu(self, monkeypatch):
        import paddle_tpu.ops.pallas.paged_attention as mod

        def boom(*a, **kw):  # the kernel must NOT run outside the fixture
            raise AssertionError("kernel path taken off-TPU")

        monkeypatch.setattr(mod, "paged_decode_attention", boom)
        rng = np.random.RandomState(6)
        q, kp, vp, pt, ln = _build_case(rng, 2, 4, 4, 8, 4, 8, 2, [3, 6])
        out = paged_attention(q, kp, vp, pt, ln)
        assert np.all(np.isfinite(np.asarray(out)))


class TestShapeValidation:
    def test_bad_shapes_raise(self):
        q = jnp.zeros((2, 4, 8))
        kp = jnp.zeros((2, 8, 4, 8))
        vp = jnp.zeros((2, 8, 4, 8))
        pt = jnp.zeros((2, 2), jnp.int32)
        ln = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="multiple of kv heads"):
            paged_attention_reference(jnp.zeros((2, 3, 8)), kp, vp, pt, ln)
        with pytest.raises(ValueError, match="head_dim"):
            paged_attention_reference(jnp.zeros((2, 4, 4)), kp, vp, pt, ln)
        with pytest.raises(ValueError, match="page_table"):
            paged_attention_reference(q, kp, vp, jnp.zeros((3, 2), jnp.int32),
                                      ln)
        with pytest.raises(ValueError, match="context_lens"):
            paged_attention_reference(q, kp, vp, pt,
                                      jnp.zeros((3,), jnp.int32))


class TestVisitCounter:
    def test_counts_equal_ceil_len_over_page(self, paged_interpret):
        lens = [0, 1, 4, 5, 17, 64]
        ps, pps = 4, 16
        got = np.asarray(page_visit_counts(lens, ps, pps))
        want = [-(-ln // ps) for ln in lens]
        assert got.tolist() == want

    def test_ragged_cost_below_dense(self, paged_interpret):
        """The utilization counter: visited fraction ==
        sum(ceil(len/ps)) / (B * pages_per_seq), well under the dense 1.0
        for a mixed-length batch."""
        lens = [5, 60, 12, 0, 25, 3, 40, 9]
        ps, pps = 8, 8
        got = np.asarray(page_visit_counts(lens, ps, pps))
        frac = got.sum() / (len(lens) * pps)
        assert frac == sum(-(-ln // ps) for ln in lens) / (len(lens) * pps)
        assert frac < 0.45


class TestVerifyFrame:
    """PR 12: the [B, T, Hq, D] speculative verify frame — per-query
    causal limits through the same scalar-prefetch page gather."""

    def _case(self, rng, t, hq, hkv, lens, dtype=np.float32):
        q3, kp, vp, pt, ln = _build_case(rng, len(lens), hq, hkv, 8, 4, 24,
                                         6, lens, dtype)
        q = jnp.asarray(rng.randn(len(lens), t, hq, 8).astype(dtype))
        return q, kp, vp, pt, ln

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (6, 2)])
    def test_fp32_kernel_matches_reference(self, paged_interpret, hq, hkv):
        rng = np.random.RandomState(0)
        q, kp, vp, pt, lens = self._case(rng, 3, hq, hkv, [9, 17, 4])
        ker = paged_decode_attention(q, kp, vp, pt, lens)
        ref = paged_attention_reference(q, kp, vp, pt, lens)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_gqa_kernel_matches_reference(self, paged_interpret):
        rng = np.random.RandomState(1)
        q, kp, vp, pt, lens = self._case(rng, 4, 8, 2, [11, 6, 20],
                                         np.float32)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        ker = paged_decode_attention(q, kp, vp, pt, lens)
        ref = paged_attention_reference(q, kp, vp, pt, lens)
        np.testing.assert_allclose(np.asarray(ker, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=1e-2, rtol=1e-2)

    def test_per_query_causal_limit_is_lens_plus_frame(self, paged_interpret):
        """Frame i must equal a plain T=1 decode at context_lens + i: the
        per-query limit is EXACTLY the plain-decode mask shifted by the
        frame index (so accepted drafts see their own K/V, later keys
        never leak backwards)."""
        rng = np.random.RandomState(2)
        q, kp, vp, pt, lens = self._case(rng, 4, 4, 2, [9, 14])
        frame = np.asarray(paged_decode_attention(q, kp, vp, pt, lens))
        for i in range(4):
            one = paged_decode_attention(q[:, i], kp, vp, pt, lens + i)
            np.testing.assert_allclose(np.asarray(one), frame[:, i],
                                       atol=1e-6, rtol=1e-6)

    def test_t1_frame_equals_decode_path(self, paged_interpret):
        rng = np.random.RandomState(3)
        q, kp, vp, pt, lens = self._case(rng, 1, 4, 4, [9, 17, 4])
        a = np.asarray(paged_decode_attention(q, kp, vp, pt, lens))
        b = np.asarray(paged_decode_attention(q[:, 0], kp, vp, pt, lens))
        assert (a[:, 0] == b).all()

    def test_inactive_rows_zero_in_frame(self, paged_interpret):
        rng = np.random.RandomState(4)
        q, kp, vp, pt, lens = self._case(rng, 3, 4, 2, [9, 0, 5])
        out = np.asarray(paged_decode_attention(q, kp, vp, pt, lens))
        assert (out[1] == 0).all()
        assert np.isfinite(out).all()
