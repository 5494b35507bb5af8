"""Fused chunked LM-head + cross-entropy parity suite (ISSUE 1 satellite).

Gates the `paddle_tpu.ops.pallas.fused_ce` custom-vjp against an unfused
fp32 reference: loss AND gradients must match to tight tolerance across
dtypes, label smoothing, ignore_index, vocab sizes not divisible by the
chunk, every chunking variant (token-chunked, vocab-chunked, pallas
interpret-mode), and mp-sharded vs single-device. Also asserts the headline
property directly: no `[tokens, vocab]`-shaped intermediate is live in the
lowered fused program (while the unfused reference demonstrably holds one).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.flags import flag, set_flags
from paddle_tpu.distributed.mesh import shard_map_compat
from paddle_tpu.ops.pallas import fused_ce
from paddle_tpu.ops.pallas.fused_ce import (fused_linear_cross_entropy_loss,
                                            resolve_bwd_chunk,
                                            resolve_chunks,
                                            softmax_cross_entropy_loss)
from paddle_tpu.tuning.blocks import last_resolution, trial_blocks

# deliberately awkward geometry: N not divisible by chunk_tokens (7),
# V not divisible by chunk_vocab (13) or the mp world (handled by padding
# the shard in the mp tests instead)
N, H, V = 24, 16, 50
IGN = -100


def _data(dtype=jnp.float32, seed=0, n=N, h=H, v=V, with_ignored=True):
    k = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(k[0], (n, h), jnp.float32).astype(dtype)
    w = (jax.random.normal(k[1], (h, v), jnp.float32) / np.sqrt(h)).astype(dtype)
    b = jax.random.normal(k[2], (v,), jnp.float32).astype(dtype)
    lab = jax.random.randint(k[3], (n,), 0, v)
    if with_ignored:
        lab = lab.at[::5].set(IGN)
    return x, w, b, lab


def _ref_nll(x, w, b, lab, eps=0.0, z_loss=0.0, v_total=None):
    """Unfused fp32 reference: materializes the full [N, V] logits."""
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    v = logits.shape[-1] if v_total is None else v_total
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.clip(lab, 0, logits.shape[-1] - 1)
    t = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    nll = lse - (1.0 - eps) * t - eps * jnp.sum(logits, axis=-1) / v
    if z_loss:
        nll = nll + z_loss * lse * lse
    return jnp.where(lab != IGN, nll, 0.0)


def _grads(fn, *args):
    return jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=tuple(
        range(len(args) - 1)))(*args)


def _tol(dtype):
    # stats/accumulators are fp32 in both paths; bf16 only rounds the
    # inputs and the returned dx/dw casts
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


class TestFusedLinearCE:
    @pytest.mark.parametrize("variant", ["tokens", "vocab", "pallas"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_loss_and_grad_parity(self, variant, dtype):
        x, w, b, lab = _data(dtype)
        bias = None if variant == "pallas" else b  # pallas path is bias-free

        def fused(x_, w_, *rest):
            b_ = rest[0] if bias is not None else None
            return fused_linear_cross_entropy_loss(
                x_, w_, lab, b_, chunk_tokens=7, chunk_vocab=13,
                variant=variant, mp_axis=None)

        args = (x, w) + ((bias,) if bias is not None else ()) + (lab,)
        ref_args = (x, w, bias, lab)
        np.testing.assert_allclose(
            fused(*args[:-1]), _ref_nll(*ref_args), **_tol(dtype))
        g_f = _grads(fused, *args)
        g_r = _grads(lambda x_, w_, *r: _ref_nll(
            x_, w_, r[0] if bias is not None else None, lab), *args)
        for gf, gr in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(gf, np.float32),
                                       np.asarray(gr, np.float32),
                                       **_tol(dtype))

    @pytest.mark.parametrize("variant", ["tokens", "vocab"])
    @pytest.mark.parametrize("eps", [0.1])
    def test_label_smoothing_and_zloss(self, variant, eps):
        x, w, b, lab = _data()

        def fused(x_, w_, b_, *rest):
            return fused_linear_cross_entropy_loss(
                x_, w_, lab, b_, label_smoothing=eps, z_loss=1e-3,
                chunk_tokens=7, chunk_vocab=13, variant=variant, mp_axis=None)

        def ref(x_, w_, b_, *rest):
            return _ref_nll(x_, w_, b_, lab, eps=eps, z_loss=1e-3)

        np.testing.assert_allclose(fused(x, w, b), ref(x, w, b),
                                   rtol=2e-5, atol=2e-5)
        for gf, gr in zip(_grads(fused, x, w, b, lab),
                          _grads(ref, x, w, b, lab)):
            np.testing.assert_allclose(gf, gr, rtol=2e-5, atol=2e-5)

    def test_ignored_tokens_zero_loss_and_grad(self):
        x, w, b, lab = _data()
        lab_all_ign = jnp.full_like(lab, IGN)
        nll = fused_linear_cross_entropy_loss(x, w, lab_all_ign,
                                              chunk_tokens=7, mp_axis=None)
        np.testing.assert_allclose(nll, np.zeros(N), atol=0)
        dx, dw = _grads(lambda x_, w_, *r: fused_linear_cross_entropy_loss(
            x_, w_, lab_all_ign, chunk_tokens=7, mp_axis=None), x, w, lab)
        np.testing.assert_allclose(dx, np.zeros_like(dx), atol=0)
        np.testing.assert_allclose(dw, np.zeros_like(dw), atol=0)

    def test_softmax_ce_on_precomputed_logits(self):
        x, w, b, lab = _data()
        logits = jnp.dot(x, w) + b

        def fused(lg):
            return softmax_cross_entropy_loss(lg, lab, chunk_tokens=7,
                                              mp_axis=None)

        def ref(lg):
            return _ref_nll(lg, jnp.eye(V, dtype=jnp.float32), None, lab)

        np.testing.assert_allclose(fused(logits), ref(logits),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            jax.grad(lambda lg: jnp.sum(fused(lg)))(logits),
            jax.grad(lambda lg: jnp.sum(ref(lg)))(logits),
            rtol=2e-5, atol=2e-5)


class TestMpShardedParity:
    """Megatron-style mp-parallel softmax: shard_map over a 4-way 'mp' axis,
    W sharded on vocab — loss and grads must match the single-device run.
    This is the parity gate `_mp_fix_grads` points at."""

    def _mesh(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-virtual-device CPU platform")
        return Mesh(np.array(jax.devices()[:4]), ("mp",))

    def test_linear_ce_mp_matches_single_device(self):
        mesh = self._mesh()
        v = 52  # 4 shards of 13
        x, w, b, lab = _data(v=v)

        def body(x_, w_, lab_):
            return fused_linear_cross_entropy_loss(
                x_, w_, lab_, chunk_tokens=7, chunk_vocab=5,
                variant="tokens", mp_axis="mp")

        sharded = shard_map_compat(body, mesh,
                                   in_specs=(P(), P(None, "mp"), P()),
                                   out_specs=P())
        np.testing.assert_allclose(sharded(x, w, lab),
                                   _ref_nll(x, w, None, lab),
                                   rtol=2e-5, atol=2e-5)
        g_f = jax.grad(lambda x_, w_: jnp.sum(sharded(x_, w_, lab)),
                       argnums=(0, 1))(x, w)
        g_r = jax.grad(lambda x_, w_: jnp.sum(_ref_nll(x_, w_, None, lab)),
                       argnums=(0, 1))(x, w)
        for gf, gr in zip(g_f, g_r):
            np.testing.assert_allclose(gf, gr, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("eps,z_loss", [(0.0, 0.0), (0.1, 1e-3)])
    def test_linear_ce_mp_deep_backward_matches_single_device(self, eps,
                                                               z_loss):
        """No chunk set: each shard's backward runs at the depth of ITS
        vocabulary slice (2 x 1280 rows of 13 columns, ragged tail)."""
        mesh = self._mesh()
        v = 52
        x, w, b, lab = _data(n=N_DEEP, v=v)

        def body(x_, w_, lab_):
            return fused_linear_cross_entropy_loss(
                x_, w_, lab_, label_smoothing=eps, z_loss=z_loss,
                variant="tokens", mp_axis="mp")

        def ref(x_, w_):
            return _ref_nll(x_, w_, None, lab, eps=eps, z_loss=z_loss)

        sharded = shard_map_compat(body, mesh,
                                   in_specs=(P(), P(None, "mp"), P()),
                                   out_specs=P())
        np.testing.assert_allclose(sharded(x, w, lab), ref(x, w),
                                   rtol=2e-5, atol=2e-5)
        assert last_resolution("fused_ce").derived == {
            "bwd_chunk_tokens": 1280, "bwd_chunk_from": "shape", "passes": 2}
        g_f = jax.grad(lambda x_, w_: jnp.sum(sharded(x_, w_, lab)),
                       argnums=(0, 1))(x, w)
        g_r = jax.grad(lambda x_, w_: jnp.sum(ref(x_, w_)),
                       argnums=(0, 1))(x, w)
        for gf, gr in zip(g_f, g_r):
            np.testing.assert_allclose(gf, gr, rtol=2e-5, atol=1e-4)

    @pytest.mark.parametrize("ct", [1.0, 2.0 ** -3])
    @pytest.mark.parametrize("reduction", ["mean", "sum", "mean_all"])
    def test_one_pass_mp_matches_two_pass(self, reduction, ct):
        """The one pass under the bound axis: the statistics of each chunk
        reduce over the vocabulary shards inside the loop, and the scalar's
        gradients are the two-pass path's bit for bit."""
        mesh = self._mesh()
        v = 52
        x, w, b, lab = _data(v=v)   # 4 chunks of 7 tokens, the last ragged
        kw = dict(label_smoothing=0.1, z_loss=1e-3, chunk_tokens=7,
                  variant="tokens", mp_axis="mp")
        specs = (P(), P(None, "mp"), P())
        one = shard_map_compat(lambda a, b_, l: fused_linear_cross_entropy_loss(
            a, b_, l, reduction=reduction, **kw), mesh, specs, P())
        two = shard_map_compat(lambda a, b_, l: fused_linear_cross_entropy_loss(
            a, b_, l, **kw), mesh, specs, P())
        l1, g1 = jax.jit(jax.value_and_grad(
            lambda a, b_: ct * one(a, b_, lab), argnums=(0, 1)))(x, w)
        assert last_resolution("fused_ce").derived["passes"] == 1
        g2 = jax.jit(jax.grad(lambda a, b_: ct * _two_pass_reduced(
            two(a, b_, lab), lab, reduction), argnums=(0, 1)))(x, w)
        for a, b_ in zip(g1, g2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
        np.testing.assert_allclose(
            float(l1) / ct,
            float(_two_pass_reduced(_ref_nll(x, w, None, lab, eps=0.1,
                                             z_loss=1e-3), lab, reduction)),
            rtol=2e-5)

    @pytest.mark.parametrize("reduction", ["mean", "mean_all"])
    def test_one_pass_under_a_gspmd_mesh(self, reduction):
        """Under a dp x mp mesh the op runs the one pass per shard in its
        own shard_map (tokens over dp, the head's vocabulary over mp) and
        sums the scalar over the token shards there."""
        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-virtual-device CPU platform")
        from jax.sharding import NamedSharding

        from paddle_tpu.distributed.mesh import set_mesh

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        x, w, b, lab = _data(n=2560, v=52)
        xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
        ws = jax.device_put(w, NamedSharding(mesh, P(None, "mp")))
        bs = jax.device_put(b, NamedSharding(mesh, P("mp")))

        def loss(a, b_, c):
            return fused_linear_cross_entropy_loss(
                a, b_, lab, c, variant="tokens", reduction=reduction)

        set_mesh(mesh)
        try:
            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            got, grads = fn(xs, ws, bs)
            assert "sdy.manual_computation" in fn.lower(xs, ws, bs).as_text()
            assert last_resolution("fused_ce").derived["passes"] == 1
        finally:
            set_mesh(None)

        def ref(a, b_, c):
            return _two_pass_reduced(_ref_nll(a, b_, c, lab), lab, reduction)

        np.testing.assert_allclose(float(got), float(ref(x, w, b)), rtol=2e-5)
        for g, r in zip(grads, jax.grad(ref, argnums=(0, 1, 2))(x, w, b)):
            np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)

    def test_sharded_logits_softmax_matches_single_device(self):
        mesh = self._mesh()
        v = 52
        x, w, b, lab = _data(v=v)
        logits = jnp.dot(x, w)

        def body(lg, lab_):
            return softmax_cross_entropy_loss(lg, lab_, chunk_tokens=7,
                                              mp_axis="mp")

        sharded = shard_map_compat(body, mesh, in_specs=(P(None, "mp"), P()),
                                   out_specs=P())
        ref = _ref_nll(logits, jnp.eye(v, dtype=jnp.float32), None, lab)
        np.testing.assert_allclose(sharded(logits, lab), ref,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            jax.grad(lambda lg: jnp.sum(sharded(lg, lab)))(logits),
            jax.grad(lambda lg: jnp.sum(_ref_nll(
                lg, jnp.eye(v, dtype=jnp.float32), None, lab)))(logits),
            rtol=2e-5, atol=2e-5)

    def test_parallel_cross_entropy_layer_fused_vs_unfused(self):
        """F.parallel_cross_entropy fused hot path vs its unfused formula,
        both under the bound mp axis."""
        mesh = self._mesh()
        v = 52
        x, w, b, lab = _data(v=v)
        logits = jnp.dot(x, w)

        def run(use_fused):
            def body(lg, lab_):
                from paddle_tpu.core.tensor import Tensor

                out = F.parallel_cross_entropy(Tensor(lg), Tensor(lab_),
                                               use_fused=use_fused)
                return out._value

            return shard_map_compat(body, mesh,
                                    in_specs=(P(None, "mp"), P()),
                                    out_specs=P())(logits, lab)

        np.testing.assert_allclose(run(True), run(False),
                                   rtol=2e-5, atol=2e-5)


# tokens enough that the backward's own depth (resolve_bwd_chunk: 2 x 1280)
# differs from the forward's chunk (all 2500 at this vocab) and leaves a
# ragged tail of 60 padded rows
N_DEEP = 2500


def _scan_out_shapes(jaxpr):
    """Shapes of every scan's outputs in `jaxpr`, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append([tuple(v.aval.shape) for v in eqn.outvars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_scan_out_shapes(sub))
    return found


class TestBackwardDepth:
    """The head's backward chunks by its own depth (resolve_bwd_chunk), not
    by the forward's tile; a chunk_tokens someone SET binds it all the same."""

    @pytest.mark.parametrize("variant", ["tokens", "pallas"])
    @pytest.mark.parametrize("eps,z_loss", [(0.0, 0.0), (0.1, 1e-3)])
    def test_deep_backward_parity(self, variant, eps, z_loss):
        x, w, _, lab = _data(n=N_DEEP)   # every fifth label is ignore_index

        def fused(x_, w_, *rest):
            return fused_linear_cross_entropy_loss(
                x_, w_, lab, label_smoothing=eps, z_loss=z_loss,
                variant=variant, mp_axis=None)

        def ref(x_, w_, *rest):
            return _ref_nll(x_, w_, None, lab, eps=eps, z_loss=z_loss)

        np.testing.assert_allclose(fused(x, w), ref(x, w),
                                   rtol=2e-5, atol=2e-5)
        res = last_resolution("fused_ce")
        assert res.derived == {"bwd_chunk_tokens": 1280,
                               "bwd_chunk_from": "shape", "passes": 2}
        assert res.values["chunk_tokens"] == N_DEEP   # the forward's tile
        assert "bwd_chunk_tokens" not in res.values   # not a tunable
        for gf, gr in zip(_grads(fused, x, w, lab), _grads(ref, x, w, lab)):
            # dw sums 2000 tokens in fp32, in another order than the
            # reference's one product
            np.testing.assert_allclose(gf, gr, rtol=2e-5, atol=1e-4)

    @pytest.mark.parametrize("n,vocab,want", [
        (12288, 32768, 2048),    # the benchmark's cell: 6 iterations
        (12288, 16384, 2048),    # its mp=2 shard: the depth, not the cap
        (12288, 92544, 640),     # InternLM2's vocabulary: the byte cap
        (5000, 32768, 1792),     # 3 even iterations, not 2048 + 2048 + 904
        (24, 50, 24),            # tiny: all tokens
    ])
    def test_resolve_bwd_chunk(self, n, vocab, want):
        got = resolve_bwd_chunk(n, vocab)
        assert got == want
        assert got * vocab * 4 <= fused_ce._BWD_TILE_BYTES
        assert got == n or got % 128 == 0

    @pytest.mark.parametrize("how", ["caller", "flag"])
    def test_set_chunk_binds_backward(self, how):
        """chunk_tokens from the caller or FLAGS_fused_ce_chunk_tokens is a
        memory bound: the lowered loss + grads hold no [rows, vocab] tile
        of more rows than it."""
        import re

        n, h, v = 96, 8, 640
        x, w, _, lab = _data(n=n, h=h, v=v, with_ignored=False)
        kw = {"chunk_tokens": 16} if how == "caller" else {}
        prev = flag("fused_ce_chunk_tokens")
        try:
            if how == "flag":
                set_flags({"fused_ce_chunk_tokens": 16})
            txt = jax.jit(jax.value_and_grad(
                lambda a, b: jnp.sum(fused_linear_cross_entropy_loss(
                    a, b, lab, variant="tokens", mp_axis=None, **kw)),
                argnums=(0, 1))).lower(x, w).as_text()
        finally:
            set_flags({"fused_ce_chunk_tokens": prev})
        assert last_resolution("fused_ce").derived == {
            "bwd_chunk_tokens": 16, "bwd_chunk_from": how, "passes": 2}
        rows = [int(r) for r in re.findall(rf"tensor<(\d+)x{v}x", txt)]
        assert rows and max(rows) == 16

    def test_forward_tile_that_nobody_set_does_not_bind(self):
        """A tuned / trial / heuristic forward tile was sized for the
        forward alone: the backward keeps the shape rule's depth."""
        x, w, _, lab = _data()
        with trial_blocks("fused_ce", {"chunk_tokens": 8, "chunk_vocab": V}):
            g = _grads(lambda a, b, *r: fused_linear_cross_entropy_loss(
                a, b, lab, variant="tokens", mp_axis=None), x, w, lab)
        res = last_resolution("fused_ce")
        assert res.provenance == "trial" and res.values["chunk_tokens"] == 8
        assert res.derived == {"bwd_chunk_tokens": N,
                               "bwd_chunk_from": "shape", "passes": 2}
        for gf, gr in zip(g, _grads(
                lambda a, b, *r: _ref_nll(a, b, None, lab), x, w, lab)):
            np.testing.assert_allclose(gf, gr, rtol=2e-5, atol=2e-5)

    def test_backward_scan_runs_ceil_n_over_depth_iterations(self):
        x, w, _, lab = _data(n=N_DEEP)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda a, b: jnp.sum(fused_linear_cross_entropy_loss(
                a, b, lab, variant="tokens", mp_axis=None)),
            argnums=(0, 1)))(x, w)
        shapes = _scan_out_shapes(jaxpr.jaxpr)
        # the stacked dx: [iterations, depth, hidden]
        assert any((2, 1280, H) in outs for outs in shapes), shapes

    def test_logits_level_backward_keeps_the_forward_chunk(self):
        """No head, no accumulator, nothing to amortise."""
        x, w, _, lab = _data()
        jax.grad(lambda lg: jnp.sum(softmax_cross_entropy_loss(
            lg, lab, mp_axis=None)))(jnp.dot(x, w))
        res = last_resolution("fused_ce")
        assert res.derived == {
            "bwd_chunk_tokens": res.values["chunk_tokens"],
            "bwd_chunk_from": "forward", "passes": 2}


def _two_pass_reduced(nll, lab, reduction):
    """The reduction the callers applied to per-token losses before the op
    took it: F.cross_entropy's mean over non-ignored tokens, sum, and
    `ParallelCrossEntropy(...).mean()` over every token."""
    if reduction == "mean":
        return jnp.sum(nll) / jnp.maximum(
            jnp.sum((lab != IGN).astype(nll.dtype)), 1.0)
    if reduction == "sum":
        return jnp.sum(nll)
    return jnp.mean(nll)


def _count_eqns(jaxpr, name):
    """Equations `name` in `jaxpr` and the jaxprs nested in it, a Pallas
    kernel's body left out."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _count_eqns(sub, name)
    return n


def _bits(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32))


class TestOnePass:
    """A reduced loss through the head: one token-chunked pass computes the
    loss and the gradients together. At a cotangent of 1 or a power of two
    its gradients are the two-pass path's bit for bit; the two-pass path is
    the per-token op reduced outside it, at the same chunk."""

    def _pair(self, reduction, extras, dtype, n=N_DEEP):
        x, w, b, lab = _data(dtype, n=n)   # every fifth label ignored
        kw = dict(variant="tokens", mp_axis=None)
        if extras:
            kw.update(label_smoothing=0.1, z_loss=1e-3)
        bias = b if extras else None

        def one(x_, w_, b_):
            return fused_linear_cross_entropy_loss(
                x_, w_, lab, b_, reduction=reduction, **kw)

        def two(x_, w_, b_):
            return _two_pass_reduced(fused_linear_cross_entropy_loss(
                x_, w_, lab, b_, **kw), lab, reduction)

        return one, two, (x, w, bias)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("ct", [1.0, 2.0 ** -3])
    @pytest.mark.parametrize("extras", [False, True],
                             ids=["plain", "bias_smoothing_zloss"])
    @pytest.mark.parametrize("reduction", ["mean", "sum", "mean_all"])
    def test_gradients_bit_equal_to_two_pass(self, reduction, extras, ct,
                                             dtype):
        # 2500 tokens: two iterations of 1280 with a ragged tail
        one, two, args = self._pair(reduction, extras, dtype)
        argnums = (0, 1, 2) if extras else (0, 1)
        g1 = jax.grad(lambda *a: ct * one(*a), argnums=argnums)(*args)
        assert last_resolution("fused_ce").derived == {
            "bwd_chunk_tokens": 1280, "bwd_chunk_from": "shape", "passes": 1}
        g2 = jax.grad(lambda *a: ct * two(*a), argnums=argnums)(*args)
        for a, b in zip(g1, g2):
            assert a.dtype == b.dtype == dtype
            np.testing.assert_array_equal(_bits(a), _bits(b))
        np.testing.assert_allclose(float(one(*args)), float(two(*args)),
                                   rtol=2e-6)

    @pytest.mark.parametrize("reduction", ["mean", "sum", "mean_all"])
    def test_other_cotangent_within_one_ulp(self, reduction):
        """0.3: the one pass scales the bf16 gradients after they are
        rounded, the two-pass path the fp32 cotangent before: one bf16
        rounding apart, beside the fp32 sums' own rounding (a few fp32 ulps
        of the largest element, which only an element that cancels to near
        zero can show)."""
        one, two, args = self._pair(reduction, True, jnp.bfloat16)
        g1 = jax.grad(lambda *a: 0.3 * one(*a), argnums=(0, 1, 2))(*args)
        g2 = jax.grad(lambda *a: 0.3 * two(*a), argnums=(0, 1, 2))(*args)
        for a, b in zip(g1, g2):
            a, b = _bits(a), _bits(b)
            ulp = np.spacing(np.maximum(np.abs(a), np.abs(b))) * 2.0 ** 16
            sums = np.max(np.abs(b)) * 2.0 ** -21
            assert np.all(np.abs(a - b) <= ulp + sums)
            assert np.mean(a != b) < 0.5

    def test_primal_makes_one_head_product(self):
        """An evaluation pays no gradient products: the un-differentiated
        reduced loss is the statistics forward alone (the Pallas `ce_stats`
        call where the variant is pallas), and the differentiated one is ONE
        scan of three products with no `ce_stats` call."""
        x, w, _, lab = _data(n=N_DEEP)

        def loss(a, b):
            return fused_linear_cross_entropy_loss(
                a, b, lab, variant="pallas", mp_axis=None, reduction="mean")

        primal = jax.make_jaxpr(loss)(x, w).jaxpr
        assert _count_eqns(primal, "pallas_call") == 1
        assert _count_eqns(primal, "dot_general") == 0
        diff = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
            x, w).jaxpr
        assert _count_eqns(diff, "pallas_call") == 0
        assert _count_eqns(diff, "scan") == 1
        assert _count_eqns(diff, "dot_general") == 3
        tokens = jax.make_jaxpr(lambda a, b: fused_linear_cross_entropy_loss(
            a, b, lab, variant="tokens", mp_axis=None, reduction="mean"))(
                x, w).jaxpr
        assert _count_eqns(tokens, "dot_general") == 1

    def test_ignored_everywhere_gives_zero(self):
        x, w, _, lab = _data()
        ign = jnp.full_like(lab, IGN)
        loss, (dx, dw) = jax.value_and_grad(
            lambda a, b: fused_linear_cross_entropy_loss(
                a, b, ign, chunk_tokens=7, mp_axis=None, reduction="mean"),
            argnums=(0, 1))(x, w)
        assert float(loss) == 0.0
        assert not np.any(np.asarray(dx)) and not np.any(np.asarray(dw))

    def test_unknown_reduction_is_refused(self):
        x, w, _, lab = _data()
        with pytest.raises(ValueError, match="reduction"):
            fused_linear_cross_entropy_loss(x, w, lab, reduction="avg")


def _model_loss(name):
    """One tiny model of each head that reaches the op, traced with labels
    (its loss is what the train step differentiates): the loss's shape."""
    from paddle_tpu import models
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.pallas.flash_attention import force_interpret

    build = {
        "llama": (models.LlamaForCausalLM, models.llama_tiny_config),
        "kimi_linear": (models.KimiLinearForCausalLM,
                        models.kimi_linear_tiny_config),
        "lfm2_moe": (models.Lfm2MoeForCausalLM, models.lfm2_moe_tiny_config),
        "deepseek_v3": (models.DeepseekV3ForCausalLM,
                        models.deepseek_v3_tiny_config),
    }[name]
    paddle.seed(0)
    cfg = build[1]()
    model = build[0](cfg)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    with force_interpret():
        return jax.eval_shape(
            lambda i: model(Tensor(i), labels=Tensor(i))._value, ids)


def _fused_head_loss(reduction):
    """`fused_head_loss`, as the pipelined runtimes call it, on a head that
    opts in (forward_features + lm_head) with a CrossEntropyLoss."""
    from paddle_tpu.parallel.fused_head import (fused_head_loss,
                                                fused_head_spec)

    class Head(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(H)
            self.lm_head = nn.Linear(H, V, bias_attr=False)

        def forward_features(self, x):
            return self.norm(x)

        def forward(self, x):
            return self.lm_head(self.forward_features(x))

    head = Head()
    spec = fused_head_spec(head, nn.CrossEntropyLoss(reduction=reduction))
    x, _, _, lab = _data()
    return fused_head_loss(head, [p._value for p in head.parameters()],
                           x, lab, spec)


class TestPassesByCaller:
    """`last_resolution("fused_ce").derived["passes"]` says which path ran:
    every head of the benchmark's cells and the pipelined runtimes' head
    give a reduction and take one pass; per-token losses and the vocab
    variant keep two."""

    @pytest.mark.parametrize("model", ["llama", "kimi_linear", "lfm2_moe",
                                       "deepseek_v3"])
    def test_model_heads_take_one_pass(self, model):
        loss = _model_loss(model)
        assert loss.shape == () and loss.dtype == jnp.float32
        assert last_resolution("fused_ce").derived["passes"] == 1

    @pytest.mark.parametrize("reduction", ["mean", "sum"])
    def test_fused_head_loss_takes_one_pass(self, reduction):
        assert np.isfinite(float(_fused_head_loss(reduction)))
        assert last_resolution("fused_ce").derived["passes"] == 1

    def test_per_token_losses_take_two(self):
        x, w, _, lab = _data()
        out = F.fused_linear_cross_entropy(
            paddle.to_tensor(np.asarray(x)), paddle.to_tensor(np.asarray(w)),
            paddle.to_tensor(np.asarray(lab)), reduction="none")
        assert tuple(out.shape) == (N,)
        assert last_resolution("fused_ce").derived["passes"] == 2

    def test_vocab_variant_takes_two(self):
        x, w, _, lab = _data()
        loss, _ = jax.value_and_grad(
            lambda a: fused_linear_cross_entropy_loss(
                a, w, lab, variant="vocab", chunk_vocab=13, mp_axis=None,
                reduction="mean"))(x)
        assert last_resolution("fused_ce").derived["passes"] == 2
        np.testing.assert_allclose(
            float(loss), float(_two_pass_reduced(_ref_nll(x, w, None, lab),
                                                 lab, "mean")), rtol=2e-5)


class TestNoFullLogitsMaterialized:
    """The acceptance-criterion inspection: the lowered fused train-style
    program (loss + grads) must hold NO [tokens, vocab]-shaped live value;
    the unfused reference must (proves the probe has teeth)."""

    def _probe(self, fn, x, w, lab):
        txt = jax.jit(lambda x_, w_: jax.value_and_grad(
            lambda a, b_: jnp.sum(fn(a, b_)), argnums=(0, 1))(x_, w_)
        ).lower(x, w).as_text()
        shapes = [f"tensor<{x.shape[0]}x{w.shape[1]}x{t}>"
                  for t in ("f32", "bf16", "f16")]
        return any(s in txt for s in shapes)

    def test_fused_has_no_tokens_by_vocab_intermediate(self):
        n, h, v = 96, 8, 640
        x, w, _, lab = _data(n=n, h=h, v=v, with_ignored=False)
        assert not self._probe(
            lambda a, b: fused_linear_cross_entropy_loss(
                a, b, lab, chunk_tokens=16, variant="tokens", mp_axis=None),
            x, w, lab)
        assert not self._probe(
            lambda a, b: fused_linear_cross_entropy_loss(
                a, b, lab, chunk_vocab=128, variant="vocab", mp_axis=None),
            x, w, lab)

    def test_unfused_reference_does_materialize(self):
        n, h, v = 96, 8, 640
        x, w, _, lab = _data(n=n, h=h, v=v, with_ignored=False)
        assert self._probe(lambda a, b: _ref_nll(a, b, None, lab), x, w, lab)


class TestFunctionalSurface:
    def test_cross_entropy_fused_matches_unfused(self):
        x, w, b, lab = _data()
        logits = paddle.to_tensor(np.asarray(jnp.dot(x, w) + b))
        label = paddle.to_tensor(np.asarray(lab))
        for red in ("mean", "sum", "none"):
            got = F.cross_entropy(logits, label, reduction=red, use_fused=True)
            want = F.cross_entropy(logits, label, reduction=red,
                                   use_fused=False)
            np.testing.assert_allclose(np.asarray(got.numpy(), np.float32),
                                       np.asarray(want.numpy(), np.float32),
                                       rtol=2e-5, atol=2e-5)

    def test_cross_entropy_fused_3d_and_trailing_label_dim(self):
        k = jax.random.key(3)
        logits = paddle.to_tensor(
            np.asarray(jax.random.normal(k, (2, 6, V), jnp.float32)))
        lab = paddle.to_tensor(
            np.asarray(jax.random.randint(k, (2, 6, 1), 0, V)))
        got = F.cross_entropy(logits, lab, use_fused=True)
        want = F.cross_entropy(logits, lab, use_fused=False)
        np.testing.assert_allclose(got.numpy(), want.numpy(),
                                   rtol=2e-5, atol=2e-5)

    def test_incubate_layer_forward_backward(self):
        from paddle_tpu.incubate.nn import FusedLinearCrossEntropy

        layer = FusedLinearCrossEntropy(H, V, has_bias=True)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(N, H).astype(np.float32))
        x.stop_gradient = False
        lab = paddle.to_tensor(
            np.random.RandomState(1).randint(0, V, size=(N,)))
        loss = layer(x, lab)
        ref = F.cross_entropy(
            paddle.matmul(x, layer.weight) + layer.bias, lab, use_fused=False)
        np.testing.assert_allclose(float(loss.numpy()), float(ref.numpy()),
                                   rtol=2e-5, atol=2e-5)
        loss.backward()
        assert layer.weight.grad is not None
        assert x.grad is not None and np.isfinite(x.grad.numpy()).all()

    def test_escape_hatch_flag(self):
        """use_fused_cross_entropy=False must route F.cross_entropy off the
        fused kernel (the jaxpr then contains a full-size log-softmax)."""
        x, w, b, lab = _data()
        logits = paddle.to_tensor(np.asarray(jnp.dot(x, w)))
        label = paddle.to_tensor(np.asarray(lab))
        prev = flag("use_fused_cross_entropy")
        try:
            set_flags({"use_fused_cross_entropy": False})
            off = F.cross_entropy(logits, label)
            set_flags({"use_fused_cross_entropy": True})
            on = F.cross_entropy(logits, label)
        finally:
            set_flags({"use_fused_cross_entropy": prev})
        np.testing.assert_allclose(on.numpy(), off.numpy(),
                                   rtol=2e-5, atol=2e-5)

    def test_llama_fused_flag_parity(self):
        """End-to-end: LlamaForCausalLM loss with the fused head+loss flag
        on vs off (same weights, same batch) — the CompiledTrainStep hot
        path vs the unfused escape hatch."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=97, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=16)
        paddle.seed(7)
        model = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 97, size=(2, 12)))
        prev = {k: flag(k) for k in ("use_fused_head_loss",
                                     "use_fused_cross_entropy")}
        try:
            set_flags({"use_fused_head_loss": True,
                       "use_fused_cross_entropy": True})
            fused = float(model(ids, labels=ids).numpy())
            set_flags({"use_fused_head_loss": False,
                       "use_fused_cross_entropy": False})
            unfused = float(model(ids, labels=ids).numpy())
        finally:
            set_flags(prev)
        np.testing.assert_allclose(fused, unfused, rtol=2e-5, atol=2e-5)

    def test_chunk_resolution(self):
        ct, cv = resolve_chunks(4096, 32000)
        assert 16 <= ct <= 4096 and ct * 32000 <= (1 << 22) + 32000
        assert resolve_chunks(10, 7, chunk_tokens=64, chunk_vocab=64) == (10, 7)
