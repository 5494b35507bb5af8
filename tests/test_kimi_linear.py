"""Kimi-Linear against its plain reference (benchmark/arch/kimi_linear/
reference.py: float32, the KDA state carried token by token, attention
materialised, experts by a plain loop), on seeded weights at toy sizes, the
Pallas kernels interpreted: KDA forward and gradients, latent attention at
query/key width 192 beside value width 128, the sigmoid router, the held
experts and the share test, and the whole five-layer model (all three kinds
of layer) through `CompiledTrainStep`, leaf by leaf.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.arch.kimi_linear import reference as KR
from benchmark.arch.kimi_linear import weights as KW
from paddle_tpu.models import KimiLinearForCausalLM, kimi_linear_tiny_config
from paddle_tpu.ops.pallas.flash_attention import (flash_attention_bshd,
                                                   force_interpret)

HI = jax.lax.Precision.HIGHEST


def tiny_cfg(**kw) -> dict:
    cfg = dataclasses.asdict(kimi_linear_tiny_config(**kw))
    cfg["dtype"] = "float32"
    return cfg


def _close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
    assert err <= tol, f"{what}: {err:.3e} > {tol}"


# ---------------------------------------------------------------------------
# KDA: the chunked kernels against the token-by-token recurrence
# ---------------------------------------------------------------------------

def _kda_inputs(t, seed=0, b=2, h=2, kd=128):
    rs = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)      # noqa: E731
    q = unit(rs.randn(b, t, h, kd)) / np.sqrt(kd)
    k, v = unit(rs.randn(b, t, h, kd)), rs.randn(b, t, h, kd)
    g = -np.exp(rs.randn(b, t, h, kd) * 0.5 - 2.0)
    beta = 1.0 / (1.0 + np.exp(-rs.randn(b, t, h)))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _kda_case(t, inputs, seed=0):
    """The inputs of a case: random, a decay that forgets within a few
    tokens, or keys that point the same way (as out of a SiLU) with a slow
    decay and beta near 1."""
    q, k, v, g, beta = _kda_inputs(t, seed=seed)
    if inputs == "strong_decay":
        g = jnp.full_like(g, -2.0)
    elif inputs == "same_way":
        k = jnp.abs(k) + 0.05
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        beta = jnp.full_like(beta, 0.95)
        g = g * 0.05
    return q, k, v, g, beta


def _kda_path(backend):
    """kda_chunked with the part before the scan on `backend`, interpreted."""
    from paddle_tpu.ops.pallas import kda

    def run(*a):
        rule = kda.chunk_backend
        kda.chunk_backend = lambda *_: backend
        try:
            return kda.kda_chunked(*a, interpret=True)
        finally:
            kda.chunk_backend = rule
    return run


def _value_and_grads(fn, args):
    def f(*a):
        o = fn(*a)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
    (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return o, grads


@pytest.mark.parametrize("t,dtype,inputs,tol", [
    pytest.param(128, "float32", "random", (2e-5, 5e-5), id="128"),
    pytest.param(150, "float32", "random", (2e-5, 5e-5), id="150"),
    pytest.param(40, "float32", "random", (2e-5, 5e-5), id="40"),
    pytest.param(128, "bfloat16", "random", (2e-2, 3e-2), id="128-bfloat16"),
    pytest.param(150, "bfloat16", "random", (2e-2, 3e-2), id="150-bfloat16"),
    pytest.param(128, "float32", "strong_decay", (1e-4, 5e-4), id="strong_decay"),
    pytest.param(192, "float32", "same_way", (1e-4, 5e-4), id="same_way"),
])
def test_kda_forward_and_gradients_match_the_recurrence(t, dtype, inputs, tol):
    """The chunk kernels (`kda_chunk_fwd` / `kda_chunk_bwd`) against the
    token-by-token recurrence and against the same part in XLA, output and
    every input's gradient. 150 and 40 are no multiple of the chunk of 64.
    In bfloat16 the products take bfloat16 operands on both paths (the
    recurrence runs in float32 on the same rounded inputs)."""
    q, k, v, g, beta = _kda_case(t, inputs)
    lo = jnp.dtype(dtype)
    args = (q.astype(lo), k.astype(lo), v.astype(lo), g, beta)
    o1, g1 = _value_and_grads(_kda_path("pallas"), args)
    o2, g2 = _value_and_grads(_kda_path("xla"), args)
    ref_args = [x.astype(jnp.float32) for x in args]
    o0, g0 = _value_and_grads(jax.vmap(KR.kda_recurrence), ref_args)
    assert o1.dtype == lo and bool(jnp.isfinite(o1.astype(jnp.float32)).all())
    _close(o1, o0, tol[0], "output")
    _close(o1, o2, tol[0] / 10 if dtype == "float32" else tol[0], "output against XLA")
    for name, a, b, c in zip(("dq", "dk", "dv", "dg", "dbeta"), g1, g0, g2):
        assert a.dtype == c.dtype and bool(jnp.isfinite(a.astype(jnp.float32)).all())
        _close(a, b, tol[1], name)
        # under strong decay XLA's beta gradient overflows (inf x 0 above the
        # diagonal of ku kn^T); the kernels select there instead
        if bool(jnp.isfinite(c.astype(jnp.float32)).all()) or inputs != "strong_decay":
            _close(a, c, tol[1] / 10 if dtype == "float32" else tol[1], name + " against XLA")


def test_kda_strong_decay_stays_finite():
    """A channel that forgets within a few tokens: exp(G_mid - G_i) is large
    and must not overflow float32 inside a chunk of 64."""
    from paddle_tpu.ops.pallas.kda import kda_chunked
    from paddle_tpu.tuning.blocks import last_resolution

    q, k, v, g, beta = _kda_case(128, "strong_decay", seed=1)
    o1 = kda_chunked(q, k, v, g, beta, interpret=True)
    assert last_resolution("kda").derived["chunk_backend"] == "pallas"
    o0 = jax.vmap(KR.kda_recurrence)(q, k, v, g, beta)
    assert bool(jnp.isfinite(o1).all())
    _close(o1, o0, 1e-4, "output under strong decay")


def test_kda_keys_that_point_the_same_way():
    """Keys out of a SiLU are mostly positive, so k_t . k_i is 0.5 and not
    0.05: a Neumann-series inverse of (I + A) cancels to NaN there (it did on
    the chip); the chunk kernels' inverse must follow the recurrence."""
    from paddle_tpu.ops.pallas.kda import kda_chunked
    from paddle_tpu.tuning.blocks import last_resolution

    q, k, v, g, beta = _kda_case(192, "same_way", seed=2)
    o1, vjp1 = jax.vjp(lambda *a: kda_chunked(*a, interpret=True), q, k, v, g, beta)
    assert last_resolution("kda").derived["chunk_backend"] == "pallas"
    o0, vjp0 = jax.vjp(jax.vmap(KR.kda_recurrence), q, k, v, g, beta)
    assert bool(jnp.isfinite(o1).all())
    _close(o1, o0, 1e-4, "output")
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), vjp1(jnp.ones_like(o1)),
                          vjp0(jnp.ones_like(o0))):
        _close(a, b, 5e-4, name)


def test_kda_inverse_is_float32_accurate():
    """The chunk kernels' (I + A)^-1 (a doubling at one bfloat16 pass, then
    two Newton steps with float32 residuals) against float64, on keys that
    point the same way with beta near 1: within a few float32 roundings,
    as XLA's triangular solve in float32 is."""
    from paddle_tpu.ops.pallas import kda

    _, vpu = kda._masks(kda.LOCAL_CHUNKS * kda.CHUNK)
    rs = np.random.RandomState(0)
    k = np.abs(rs.randn(vpu.shape[1], 128)) + 0.05
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    a = np.asarray(vpu[kda._STRICT]) * (0.999 * (k @ k.T))
    exact = np.linalg.inv(np.eye(len(a)) + a)
    t = np.asarray(kda._unit_lower_inverses(jnp.asarray(a, jnp.float32)[None], vpu)[0],
                   np.float64)
    xla = np.asarray(jax.scipy.linalg.solve_triangular(
        jnp.eye(len(a)) + jnp.asarray(a, jnp.float32), jnp.eye(len(a)), lower=True,
        unit_diagonal=True), np.float64)
    err = np.abs(t - exact).max() / np.abs(exact).max()
    assert err < 3e-7 and err < 4 * np.abs(xla - exact).max() / np.abs(exact).max(), err


def _kernel_eqns(fn, args):
    """Equations of each Pallas kernel body in fn's jaxpr, by kernel name,
    nested jaxprs (a fori_loop's body) counted once."""
    from jax._src import core

    counts = {}

    def size(jaxpr):
        return sum(1 + sum(size(sub) for sub in core.jaxprs_in_params(e.params))
                   for e in jaxpr.eqns)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]] = size(eqn.params["jaxpr"])
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


def _row_loop_kernel(x_ref, o_ref):
    """A forward substitution written as a Python loop over a chunk's rows:
    what the chunk kernels must not be."""
    x = x_ref[...]
    for r in range(1, x.shape[0]):
        x = x.at[r].add(-x[r - 1] * 0.5)
    o_ref[...] = x


@pytest.mark.parametrize("kernel", ["kda_chunk_fwd", "kda_chunk_bwd", "rows_control"])
def test_kda_chunk_kernels_do_not_unroll_the_chunk(kernel, monkeypatch):
    """Setting-up cost: a Pallas kernel is traced and lowered at every call
    site on every set-up, so its body must not grow with the chunk. The
    chunk kernels' bodies hold the same equations at a chunk of 32 as of 64
    (the inverse's steps are a `fori_loop`); a body that loops over the
    rows in Python (the control) does not."""
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas import kda

    counts = []
    for chunk in (32, 64):
        monkeypatch.setattr(kda, "CHUNK", chunk)
        if kernel == "rows_control":
            fn = lambda x: pl.pallas_call(                     # noqa: E731
                _row_loop_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True, name="rows_control")(x)
            got = _kernel_eqns(fn, (jnp.ones((chunk, 128), jnp.float32),))
        else:
            args = _kda_case(4 * chunk, "random")
            got = _kernel_eqns(lambda *a: jax.grad(
                lambda *x: jnp.sum(kda.kda_chunked(*x, interpret=True)),
                argnums=(0, 1, 2, 3, 4))(*a), args)
        counts.append(got[kernel])
    if kernel == "rows_control":
        assert counts[1] > 1.8 * counts[0], counts
    else:
        assert counts[0] == counts[1], counts
        assert counts[1] < 2500, counts


def test_kda_resolution_is_recorded():
    from paddle_tpu.ops.pallas.kda import CHUNK, LOCAL_CHUNKS, chunk_backend, kda_chunked
    from paddle_tpu.tuning.blocks import last_resolution

    kda_chunked(*_kda_inputs(64), interpret=True)
    res = last_resolution("kda")
    assert res.values == {"chunk": CHUNK, "head_block": 4}
    assert res.derived["grid"] == (1, 2) and res.derived["state_block"] == (4, 128, 128)
    assert res.derived["chunk_backend"] == "pallas"
    assert res.derived["local_block"] == (4, 1, LOCAL_CHUNKS * CHUNK, 128)
    # compiled for a chip: the kernels at widths of whole lanes, XLA elsewhere
    assert chunk_backend(128, 128, False) == "pallas"
    assert chunk_backend(64, 128, False) == chunk_backend(128, 96, False) == "xla"
    assert chunk_backend(64, 96, True) == "pallas"


def _kda_layer_grad(recompute, dtype):
    """The gradient, by the input and every leaf, of one KDA layer of the tiny
    config over 2 rows of 128 tokens; its arguments; their names."""
    from paddle_tpu.models.kimi_linear import KimiDeltaAttention
    from paddle_tpu.parallel import functional_call

    paddle.seed(3)
    layer = KimiDeltaAttention(kimi_linear_tiny_config(recompute=recompute))
    rs = np.random.RandomState(3)
    names, params = zip(*layer.named_parameters())
    leaves = [jnp.asarray(p.numpy() + 0.05 * rs.randn(*p.shape), dtype) for p in params]
    x = jnp.asarray(rs.randn(2, 128, 64), dtype)

    def loss(x, *w):
        out = functional_call(layer, w, (x,))._value
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return (jax.grad(loss, argnums=tuple(range(len(leaves) + 1))), (x, *leaves),
            ("x", *names))


def _pallas_calls(jaxpr, counts):
    from jax._src import core

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] = counts.get(eqn.params["name"], 0) + 1
        for sub in core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, counts)
    return counts


def test_kda_layer_runs_the_scan_twice_a_step_not_three_times():
    """A row keeps the scan's output beside its input, so its backward does
    not run `kda_chunked` forward for `o`: the chunks are made once more, in
    `kda_chunked`'s own backward (the count the device trace shows as
    `kda_fwd` events a step: PERF.md section 5)."""
    with force_interpret():
        for recompute in (True, False):
            grad, args, _ = _kda_layer_grad(recompute, jnp.float32)
            counts = _pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr, {})
            assert counts == {"kda_fwd": 2, "kda_bwd": 1, "kda_chunk_fwd": 2,
                              "kda_chunk_bwd": 1}, (recompute, counts)


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 1e-2)])
def test_kda_layer_gradients_do_not_depend_on_what_is_kept(dtype, tol):
    """`recompute=True` (a row's input and `o` kept) against `recompute=False`
    (everything kept): the kept `o` IS the one the backward would make again,
    so both read 0 apart here; the room is for another fusion's rounding."""
    with force_interpret():
        grads = []
        for recompute in (True, False):
            grad, args, names = _kda_layer_grad(recompute, jnp.dtype(dtype))
            grads.append(jax.jit(grad)(*args))
    for name, a, b in zip(names, *grads):
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()) and float(jnp.abs(b).max()) > 0
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol, name)


# ---------------------------------------------------------------------------
# latent attention: query/key width 192, value width 128
# ---------------------------------------------------------------------------

def test_flash_192_128_matches_materialised_attention():
    rs = np.random.RandomState(0)
    s, h = 256, 2
    q, k = (jnp.asarray(rs.randn(1, s, h, 192), jnp.float32) for _ in range(2))
    v = jnp.asarray(rs.randn(1, s, h, 128), jnp.float32)

    def ref(q, k, v):
        return jax.vmap(lambda a, b, c: KR.attention(a, b, c, block=64))(q, k, v)

    def vg(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))

    with force_interpret():
        l1, g1 = vg(lambda *a: flash_attention_bshd(*a, causal=True))(q, k, v)
    l0, g0 = vg(ref)(q, k, v)
    assert flash_attention_bshd(q, k, v, causal=True, interpret=True).shape == (1, s, h, 128)
    _close(l1, l0, 1e-5, "loss")
    for name, a, b in zip(("dq", "dk", "dv"), g1, g0):
        _close(a, b, 2e-5, name)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_sigmoid_router_choice_and_weights():
    """The bias moves the choice and never the weight; weights are
    scale * s / sum of the chosen s."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import _route

    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(6, 16), jnp.float32)
    bias = jnp.asarray(rs.randn(16), jnp.float32)
    routing = (("kind", "sigmoid"), ("routed_scale", 2.446), ("renormalize", True))
    topv, topi, _ = _route(logits, None, k=4, routing=routing, bias=bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    for row in range(6):
        want = np.argsort(-(s[row] + np.asarray(bias)))[:4]
        assert set(np.asarray(topi[row])) == set(want)
        ws = s[row][np.asarray(topi[row])]
        np.testing.assert_allclose(np.asarray(topv[row]), 2.446 * ws / ws.sum(), rtol=1e-6)
    w_ref, idx = KR.route(logits, bias, 4, 2.446)
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(topi)))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w_ref), np.asarray(topi), 1),
                               np.asarray(topv), rtol=1e-6)
    # no bias: plain top-k of the scores, unnormalised weights on request
    topv2, topi2, _ = _route(logits, None, k=2, routing=(("kind", "sigmoid"),
                                                         ("renormalize", False)))
    np.testing.assert_allclose(np.asarray(topv2), np.sort(s, axis=1)[:, ::-1][:, :2], rtol=1e-6)


# ---------------------------------------------------------------------------
# held experts
# ---------------------------------------------------------------------------

def _moe_leaves(cfg, seed=0, first=0):
    d = KW.dims(cfg)
    rs = np.random.RandomState(seed)
    h, e = d["h"], d["expert"]
    mk = lambda *s: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)       # noqa: E731
    full = {"post_norm": jnp.ones((h,)), "w_gate": mk(d["experts"], h, e),
            "w_up": mk(d["experts"], h, e), "w_down": mk(d["experts"], e, h),
            "shared_gate": mk(h, e), "shared_up": mk(h, e), "shared_down": mk(e, h),
            "router": mk(h, d["experts"])}
    return full


def _program_layer(cfg, full, first, bias):
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE

    d = KW.dims(cfg)
    layer = HeldExpertsMoE(d["h"], d["experts"], d["expert"], d["top_k"],
                           held_experts=(first, first + d["held"]),
                           routed_scale=cfg["routed_scaling_factor"], block_rows=8)
    held = slice(first, first + d["held"])
    for p, v in ((layer.w_gate, full["w_gate"][held]), (layer.w_up, full["w_up"][held]),
                 (layer.w_down, full["w_down"][held]), (layer.shared_gate, full["shared_gate"]),
                 (layer.shared_up, full["shared_up"]), (layer.shared_down, full["shared_down"]),
                 (layer.gate.gate_weight, full["router"])):
        p._set_value(v)
    layer.gate.e_score_correction_bias._set_value(jnp.asarray(bias, jnp.float32))
    return layer


def test_shares_add_up():
    """THE share test: over all shares of a layer, the held parts, with the
    shared expert counted once, add up to the uncut reference's layer."""
    cfg = tiny_cfg()
    d = KW.dims(cfg)
    full = _moe_leaves(cfg)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(48, d["h"]), jnp.float32)
    bias = jnp.asarray(rs.randn(d["experts"]) * 0.1, jnp.float32)
    whole = dict(cfg, num_experts=d["experts"], router_experts=d["experts"])
    want = KR.moe_layer(x, full, KW.dims(whole), whole, KR.R.mm_f32, bias=bias)[0] - x
    y = KR.R.rmsnorm(x, full["post_norm"], cfg["rms_norm_eps"])
    shared = KR.swiglu(y, full["shared_gate"], full["shared_up"], full["shared_down"],
                       KR.R.mm_f32)
    total, slots = 0.0, 0.0
    n_shares = d["experts"] // d["held"]
    with force_interpret():
        for share in range(n_shares):
            layer = _program_layer(cfg, full, share * d["held"], bias)
            out = layer(paddle.to_tensor(np.asarray(y)))
            total = total + out._value
            stats = np.asarray(layer.step_stats._value)
            slots += stats[0]
            assert stats[3] == 0.0                      # nothing dropped
    assert slots == 48 * d["top_k"]                     # every pair lands on one share
    _close(total - (n_shares - 1) * shared, want, 2e-5, "sum of the shares")


@pytest.mark.parametrize("backend,rows", [("xla", 160), ("pallas", 160), ("pallas", 96)])
def test_held_experts_follow_the_reference_when_every_token_lands_here(backend, rows):
    """A router pushed onto the held experts, 160 pairs: with 160 rows laid
    out, outputs and gradients are the plain loop's and nothing is left out;
    with 96 the 64 pairs past them are COUNTED (`stats[3]`, `moe.dropped`)."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import _held_moe

    cfg = tiny_cfg()
    d = KW.dims(cfg)
    full = _moe_leaves(cfg, seed=2)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(40, d["h"]), jnp.float32)
    bias = jnp.zeros((d["experts"],)).at[:d["held"]].set(5.0)      # all four chosen
    held = slice(0, d["held"])
    args = (full["w_gate"][held], full["w_up"][held], full["w_down"][held],
            full["shared_gate"], full["shared_up"], full["shared_down"])
    routing = (("kind", "sigmoid"), ("routed_scale", cfg["routed_scaling_factor"]),
               ("renormalize", True))

    def prog(x, router, *w):
        out, stats, load = _held_moe(x, KR.R.mm_f32(x, router), bias, *w, k=d["top_k"],
                                     first=0, routing=routing, rows=rows, block_rows=8,
                                     backend=backend)
        return jnp.sum(jnp.sin(out)), (stats, load)

    def ref(x, router, wg, wu, wd, sg, su, sd):
        w, _ = KR.route(KR.R.mm_f32(x, router), bias, d["top_k"], cfg["routed_scaling_factor"])
        out = KR.swiglu(x, sg, su, sd, KR.R.mm_f32)
        for e in range(d["held"]):
            out = out + w[:, e, None] * KR.swiglu(x, wg[e], wu[e], wd[e], KR.R.mm_f32)
        return jnp.sum(jnp.sin(out))

    argnums = tuple(range(8))
    with force_interpret():
        (l1, (stats, load)), g1 = jax.value_and_grad(prog, argnums=argnums, has_aux=True)(
            x, full["router"], *args)
    l0, g0 = jax.value_and_grad(ref, argnums=argnums)(x, full["router"], *args)
    assert float(stats[0]) == 40 * d["top_k"] and float(stats[1]) == 40.0
    assert float(stats[3]) == 160 - rows
    np.testing.assert_array_equal(np.asarray(load), [40.0] * d["held"] + [0.0] * (
        d["experts"] - d["held"]))
    if rows < 160:
        assert abs(float(l1) - float(l0)) > 1e-3        # pairs left out show
        return
    _close(l1, l0, 1e-5, "loss")
    for i, (a, b) in enumerate(zip(g1, g0)):
        _close(a, b, 5e-5, f"gradient {i}")


def test_held_rows_are_every_pair_or_a_multiple_of_the_share():
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    assert held_rows(131072, 8, 256) == (16384, 128)          # the cell: 4 x 4096
    assert held_rows(160, 4, 4, 8) == (160, 8)                # all held: dropless
    assert held_rows(160, 4, 16, 8, 2.0) == (80, 8)
    assert held_rows(160, 4, 16, 8, 100.0) == (160, 8)        # never more than the pairs


def test_a_frozen_router_takes_no_gradient_and_the_bias_keeps_float32():
    """A share's router is frozen the way any parameter is (`stop_gradient`);
    the reference stops the gradient at the router's weights too. The
    correction bias stays float32 under `to(bfloat16)` and no gradient
    reaches it."""
    cfg = tiny_cfg()
    d = KW.dims(cfg)
    full = _moe_leaves(cfg, seed=4)
    x = np.random.RandomState(5).randn(24, d["h"]).astype(np.float32)
    bias = jnp.zeros((d["experts"],))
    outs = {}
    for train in (True, False):
        layer = _program_layer(cfg, full, 0, bias)
        layer.gate.gate_weight.stop_gradient = not train
        out = layer(paddle.to_tensor(x))
        out.sum().backward()
        grad = layer.gate.gate_weight.grad
        outs[train] = np.asarray(out._value)
        moved = grad is not None and float(jnp.abs(grad._value).max()) > 0
        assert moved == train
        assert layer.gate.e_score_correction_bias.grad is None
    np.testing.assert_array_equal(outs[True], outs[False])
    held = {k: (v[:d["held"]] if k in ("w_gate", "w_up", "w_down") else v) for k, v in full.items()}
    g = jax.grad(lambda r: jnp.sum(KR.moe_layer(
        jnp.asarray(x), dict(held, router=r), d, cfg, KR.R.mm_f32)[0]))(full["router"])
    assert float(jnp.abs(g).max()) == 0.0
    layer.to(dtype="bfloat16")
    assert layer.gate.e_score_correction_bias._value.dtype == jnp.float32
    assert layer.gate.gate_weight._value.dtype == jnp.bfloat16


def test_the_balancing_rule_moves_the_bias_against_the_load():
    from paddle_tpu.incubate.distributed.models.moe import SigmoidGate

    gate = SigmoidGate(8, 4, topk=2, bias_update_rate=0.01)
    load = jnp.asarray([10.0, 2.0, 4.0, 0.0])            # mean 4
    got = np.asarray(gate.balanced(paddle.to_tensor(np.asarray(load)))._value)
    np.testing.assert_allclose(got, [-0.01, 0.01, 0.0, 0.01])
    np.testing.assert_allclose(np.asarray(KR.next_biases(jnp.zeros((1, 4)), load[None], 0.01))[0],
                               got)


def test_grouped_matmul_aligned_is_the_general_kernel_on_aligned_rows():
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rs = np.random.RandomState(0)
    gids = jnp.asarray(np.repeat([0, 0, 2, 3, 4, 4], 8), jnp.int32)   # 4 = not here
    x = jnp.asarray(rs.randn(48, 16), jnp.float32)
    w = jnp.asarray(rs.randn(4, 16, 24), jnp.float32)

    def vg(aligned):
        return jax.value_and_grad(lambda a, b: jnp.sum(jnp.sin(grouped_matmul(
            a, b, gids, block_rows=8, backend="pallas", aligned=aligned))), argnums=(0, 1))

    with force_interpret():
        l1, g1 = vg(True)(x, w)
        l0, g0 = vg(False)(x, w)
    _close(l1, l0, 1e-6)
    for a, b in zip(g1, g0):
        _close(a, b, 1e-6)
    assert float(jnp.abs(g1[1][1]).max()) == 0.0        # group 1 holds no row


# ---------------------------------------------------------------------------
# the model: five layers, all three kinds, through CompiledTrainStep
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Two AdamW steps of the program (kernels interpreted) and of the
    reference from the same seed."""
    from paddle_tpu.parallel import CompiledTrainStep

    cfg, seed, lr = tiny_cfg(router_bias_update_rate=0.01), 11, 3e-3
    rs = np.random.RandomState(5)
    batches = rs.randint(0, cfg["vocab_size"], (2, 2, 97)).astype(np.int32)
    with force_interpret():
        model = KW.seeded_model(cfg, seed)
        model.train()
        opt = paddle.optimizer.AdamW(learning_rate=lr, parameters=model.parameters(),
                                     weight_decay=0.01, multi_precision=True)
        step = CompiledTrainStep(model, lambda out, lab: out, optimizer=opt,
                                 collect_metrics=True)
        losses, first_m = [], None
        for b in batches:
            ids, lab = paddle.to_tensor(b[:, :-1]), paddle.to_tensor(b[:, 1:])
            losses.append(float(step(ids, lab, lab)))
            if first_m is None:
                # a frozen leaf (router, correction bias) keeps no moments
                first_m = [np.asarray(st["m"]) if st else np.zeros(v.shape)
                           for st, v in zip(step._opt_states, step._param_vals)]
        step.drain()
        params = [np.asarray(v) for v in step._param_vals]
        counters = step.host_counters()
        metrics = step.last_metrics()
    ref = KR.train_steps(cfg, seed, [(b[:, :-1], b[:, 1:]) for b in batches], lr,
                         param_dtype="float32")
    start = [np.asarray(x) for x in KW.W.make_all(seed, KW.leaf_specs(cfg), "float32")]
    frozen = KW.frozen(KW.leaf_specs(cfg))
    return {"cfg": cfg, "losses": losses, "grads": [m / 0.1 for m in first_m],
            "change": [0.0 if f else np.sqrt(np.sum((p - s) ** 2))
                       for p, s, f in zip(params, start, frozen)],
            "biases": [p for p, (name, *_) in zip(params, KW.leaf_specs(cfg))
                       if name.endswith("router_bias")],
            "routers": [(p, s) for p, s, (name, *_) in zip(params, start, KW.leaf_specs(cfg))
                        if name.endswith(".router")],
            "ref": ref, "counters": counters, "metrics": metrics}


def test_model_has_all_three_kinds_of_layer():
    cfg = tiny_cfg()
    assert KW.layer_kinds(cfg) == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"),
                                   ("mla", "moe"), ("kda", "moe")]
    model = KimiLinearForCausalLM(KW.program_config(cfg))
    assert [tuple(p.shape) for p in model.parameters()] == [s[1] for s in KW.leaf_specs(cfg)]


def test_model_losses_match_the_reference(trained):
    for a, b in zip(trained["losses"], trained["ref"]["losses"]):
        assert abs(a - b) / b < 2e-5, (a, b)


def test_model_every_leafs_gradient_matches_the_reference(trained):
    ref = trained["ref"]["grad_norms"]
    got = np.array([np.sqrt(np.sum(g.astype(np.float64) ** 2)) for g in trained["grads"]])
    floor = np.median(ref)
    for name, a, b in zip(trained["ref"]["leaves"], got, ref):
        assert abs(a - b) / max(b, floor) < 2e-4, (name, a, b)


def test_model_two_adamw_steps_match_the_reference(trained):
    ref = trained["ref"]["change_norms"]
    for name, a, b in zip(trained["ref"]["leaves"], trained["change"], ref):
        assert abs(a - b) / max(b, np.median(ref)) < 1e-3, (name, a, b)


def test_the_step_moves_the_bias_as_the_reference_and_leaves_the_router(trained):
    """Two steps of the balancing rule inside `CompiledTrainStep`: every
    expert layer's bias is the reference's, a multiple of the rate, and not
    all zero; the frozen router's weights are the seed's."""
    want = trained["ref"]["biases"]
    assert len(trained["biases"]) == len(want) == 4
    for got, ref in zip(trained["biases"], want):
        assert got.dtype == np.float32 and np.abs(got).max() > 0
        np.testing.assert_allclose(got, ref, atol=1e-7)
    for now, start in trained["routers"]:
        np.testing.assert_array_equal(now, start)


def test_step_counters_carry_the_expert_load(trained):
    """moe.routed_slots, moe.max_expert_load, moe.mean_expert_load and
    moe.dropped, read with the loss."""
    moe = trained["counters"]["moe"]
    d = KW.dims(trained["cfg"])
    assert moe["steps"] == 2 and moe["dropped"] == 0.0
    pairs = 2 * 96 * d["top_k"] * 4            # rows x tokens x k x expert layers
    assert 0 < moe["routed_slots"] / 2 <= pairs
    assert moe["max_expert_load"] >= moe["mean_expert_load"] > 0
    assert trained["metrics"]["moe_dropped"] == 0.0 and trained["metrics"]["moe_routed_slots"] > 0


@pytest.mark.parametrize("groups", [2, 9, 16, 40])
def test_order_by_group_is_the_stable_argsort(groups):
    """Few groups are ordered by counting (a sort of 131,072 keys takes the
    chip's compiler 36 s), many by `argsort`: the same permutation."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import _order_by_group

    g = jnp.asarray(np.random.RandomState(groups).randint(0, groups, 3000), jnp.int32)
    counts = jnp.zeros((groups,), jnp.int32).at[g].add(1)
    np.testing.assert_array_equal(np.asarray(_order_by_group(g, counts)),
                                  np.asarray(jnp.argsort(g)))


@pytest.mark.parametrize("rows", [None, 64, 24])
def test_ragged_layout_bounds_its_buffer_by_rows(rows):
    """`rows` cuts the layout to the first sorted copies (the routed ones):
    the same places as the whole layout gives them, a buffer of
    round_up(rows) + E * bm rows, and the copies past it left to the caller."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import ragged_layout

    rs = np.random.RandomState(7)
    gids = jnp.asarray(np.where(rs.rand(200) < 0.2, rs.randint(0, 3, 200), 3), jnp.int32)
    whole = ragged_layout(gids, 3, 8)
    order, rank, dest, gbuf, counts = ragged_layout(gids, 3, 8, rows=rows)
    n = 200 if rows is None else rows
    assert order.shape == rank.shape == dest.shape == (n,)
    assert gbuf.shape == (-(-n // 8) * 8 + 3 * 8,)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(whole[4]))
    for got, want in zip((order, rank, dest), whole[:3]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[:n])
    here = int(np.asarray(counts).sum())
    kept = np.asarray(jnp.take(gids, order)) < 3
    assert kept.sum() == min(here, n) and (np.asarray(dest)[kept] < gbuf.shape[0]).all()
