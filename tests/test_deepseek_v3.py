"""The DeepSeek-V3 family (Moonlight) against its plain reference
(benchmark/arch/deepseek_v3/reference.py: float32, attention materialised,
the rotation pair by pair, experts by a plain loop), on seeded weights at toy
sizes with the Pallas kernels interpreted: the rotation of the key channels
beside the latent, the shared key's gradient, the router at six of 64 with
its scale, the shares of an expert layer with two shared experts, and the
whole model (latent attention in every layer) through `CompiledTrainStep`,
leaf by leaf. The one `LatentAttention` is Kimi-Linear's too: a test holds
that model's layer to what it computed before the class could rotate.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.arch.deepseek_v3 import reference as DR
from benchmark.arch.deepseek_v3 import weights as DW
from paddle_tpu.models import (DeepseekV3Config, DeepseekV3ForCausalLM,
                               deepseek_v3_tiny_config, kimi_linear_tiny_config)
from paddle_tpu.ops.pallas.flash_attention import force_interpret

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**kw) -> dict:
    cfg = dataclasses.asdict(deepseek_v3_tiny_config(**kw))
    cfg["dtype"] = "float32"
    return cfg


def _close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
    assert err <= tol, f"{what}: {err:.3e} > {tol}"


# ---------------------------------------------------------------------------
# the configuration: the published stack and the cut
# ---------------------------------------------------------------------------

def test_the_cut_reads_published_layers_0_to_5():
    """27 layers of latent attention, layer 0 dense; the benchmark's
    configuration is layers 0-5 with every width as published."""
    whole = DeepseekV3Config()
    assert whole.layer_kinds() == ["dense"] + ["moe"] * 26
    with open(os.path.join(ROOT, "benchmark", "configs", "moonlight-16b-a3b.json")) as f:
        cut = json.load(f)
    assert DW.layer_kinds(cut) == [("mla", "dense")] + [("mla", "moe")] * 5
    assert DW.program_config(cut).layer_kinds() == whole.layer_kinds()[:6]
    for key, width in (("hidden_size", 2048), ("num_attention_heads", 16),
                       ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 128), ("kv_lora_rank", 512),
                       ("intermediate_size", 11264), ("moe_intermediate_size", 1408),
                       ("num_experts_per_tok", 6), ("n_shared_experts", 2),
                       ("rope_theta", 50000)):
        assert cut[key] == width == getattr(whole, key), key
    assert cut["router_experts"] == 64 == whole.n_routed_experts
    params = sum(int(np.prod(s[1])) for s in DW.leaf_specs(cut))
    assert round(params / 1e6) == 669 and round(params * 14 / 1e9, 2) == 9.36


@pytest.mark.parametrize("control", [False, True])
def test_the_reference_is_float32_where_the_package_turned_x64_on(control):
    """`import paddle_tpu` enables x64, and one numpy float64 scalar in the
    reference then makes what it touches float64: at the cell's size the chip
    emulated every attention block's scores, softmax and second product, and
    the reference took 334 s of a run the check stops at 360 (PERF.md section
    6, PR 37). No value of the batch's loss and gradients, as the benchmark's
    process traces them, is float64; the float8 control's neither."""
    assert jax.config.jax_enable_x64
    cfg = tiny_cfg()
    leaves = [jax.ShapeDtypeStruct(s[1], jnp.float32) for s in DW.leaf_specs(cfg)]
    ids = jax.ShapeDtypeStruct((2, 96), jnp.int32)
    mm = DR.R.mm_fp8 if control else DR.R.mm_f32
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda lv, i, l: DR.batch_loss(lv, i, l, cfg, mm), has_aux=True))(leaves, ids, ids)

    def wide(jp):
        for e in jp.eqns:
            yield from (f"{e.primitive.name} {v.aval.str_short()}" for v in e.outvars
                        if getattr(v.aval, "dtype", None) == jnp.float64)
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from wide(sub)

    assert not list(wide(jaxpr.jaxpr))


@pytest.mark.parametrize("key, value, queue", [("q_lora_rank", 1536, "B-M4"),
                                               ("n_group", 8, "B-M3")])
def test_what_is_not_built_is_refused_by_name(key, value, queue):
    with pytest.raises(NotImplementedError, match=queue):
        DeepseekV3ForCausalLM(deepseek_v3_tiny_config(**{key: value}))
    with pytest.raises(NotImplementedError, match=queue):
        DW.leaf_specs(tiny_cfg(**{key: value}))


# ---------------------------------------------------------------------------
# latent attention: the rotated channels and the one key all heads share
# ---------------------------------------------------------------------------

def test_the_rotation_is_the_references_pair_by_pair_and_position_by_position():
    """Pair (2i, 2i + 1) of position t turned by t * theta^(-2i / 64): the
    reference's `rotate` by hand, and the program's `rotate_pairs` the same
    pairs laid out [even | odd]."""
    from paddle_tpu.models.kimi_linear import rotate_pairs

    rs = np.random.RandomState(0)
    x = rs.randn(40, 3, 64).astype(np.float32)
    theta = 50000.0
    ref = np.asarray(DR.rotate(jnp.asarray(x), theta))
    for t, i in ((0, 0), (1, 0), (7, 5), (39, 31), (23, 16)):
        ang = t * theta ** (-2.0 * i / 64)
        c, s = np.cos(ang), np.sin(ang)
        a, b = x[t, :, 2 * i].astype(np.float64), x[t, :, 2 * i + 1].astype(np.float64)
        np.testing.assert_allclose(ref[t, :, 2 * i], a * c - b * s, atol=2e-5)
        np.testing.assert_allclose(ref[t, :, 2 * i + 1], b * c + a * s, atol=2e-5)
    np.testing.assert_array_equal(ref[0], x[0])                     # position 0 stays
    # a rotation: every pair keeps its length
    np.testing.assert_allclose(ref[..., 0::2] ** 2 + ref[..., 1::2] ** 2,
                               x[..., 0::2] ** 2 + x[..., 1::2] ** 2, rtol=1e-4)
    got = np.asarray(rotate_pairs(jnp.asarray(x)[None], theta))[0]  # [B, T, H, 64]
    np.testing.assert_allclose(got[..., :32], ref[..., 0::2], atol=5e-5)
    np.testing.assert_allclose(got[..., 32:], ref[..., 1::2], atol=5e-5)
    # so a rotated query times a rotated key is the interleaved layout's
    y = rs.randn(40, 1, 64).astype(np.float32)
    mine = np.einsum("thc,tc->th", got, np.asarray(rotate_pairs(jnp.asarray(y)[None], theta))[0, :, 0])
    theirs = np.einsum("thc,tc->th", ref, np.asarray(DR.rotate(jnp.asarray(y), theta))[:, 0])
    np.testing.assert_allclose(mine, theirs, atol=1e-4)


def _attention_layer(cfg_obj, seed=4, spread=0.3):
    """A `LatentAttention` with weights far enough from their start that
    attention is not uniform, and the same leaves for the reference."""
    from paddle_tpu.models.kimi_linear import LatentAttention

    rs = np.random.RandomState(seed)
    layer = LatentAttention(cfg_obj, None if getattr(cfg_obj, "mla_use_nope", False)
                            else cfg_obj.rope_theta)
    lw = {}
    for name, p in layer.named_parameters():
        lw[name] = jnp.asarray(p.numpy() + spread * rs.randn(*p.shape), jnp.float32)
        p._set_value(lw[name])
    return layer, lw, rs


def test_attention_layer_matches_the_reference_and_leaving_the_rotation_out_is_told():
    cfg = tiny_cfg()
    d = DW.dims(cfg)
    layer, lw, rs = _attention_layer(DW.program_config(cfg))
    assert layer.theta == 50000.0
    x = jnp.asarray(rs.randn(2, 128, d["h"]), jnp.float32)
    with force_interpret():
        got = layer(paddle.to_tensor(np.asarray(x)))._value
    want, unrotated = (jax.vmap(lambda r, on=on: DR.mla_layer(
        r, lw, d, cfg["rms_norm_eps"], DR.R.mm_f32, rotated=on))(x) for on in (True, False))
    _close(got, want, 2e-5, "layer")
    # the same layer WITHOUT R_t is another function by far more than that
    gap = float(jnp.abs(unrotated - want).max() / jnp.abs(want).max())
    assert gap > 100 * 2e-5, gap
    with pytest.raises(AssertionError):
        _close(got, unrotated, 2e-5)
    # position 0 is not turned and sees only itself: the two agree there
    _close(unrotated[:, 0], want[:, 0], 1e-6, "position 0")


def test_the_layer_says_what_it_resolved():
    from paddle_tpu.tuning.blocks import last_resolution

    cfg = tiny_cfg()
    layer, _, rs = _attention_layer(DW.program_config(cfg))
    with force_interpret():
        layer(paddle.to_tensor(rs.randn(1, 128, cfg["hidden_size"]).astype(np.float32)))
    res = last_resolution("latent_attention")
    assert res.values == {"heads": 2, "qk_nope": 32, "qk_rope": 16, "v": 32, "latent": 32}
    assert res.derived["rotated_channels"] == 16 and res.derived["rope_theta"] == 50000.0
    assert set(res.derived["flash_blocks"]) == {"block_q", "block_k"}
    # Kimi-Linear's layer is the same class and says it does not rotate
    nope, _, _ = _attention_layer(kimi_linear_tiny_config())
    assert nope.theta is None
    nope(paddle.to_tensor(rs.randn(1, 16, 64).astype(np.float32)))
    res = last_resolution("latent_attention")
    assert res.derived["rotated_channels"] == 0 and res.derived["rope_theta"] is None


def test_the_shared_keys_gradient_is_the_sum_over_heads():
    """`w_kva`'s 16 rope columns make ONE key that both heads read after the
    rotation: the layer's gradient by them equals the reference's, and the
    reference's is the sum of what each head's copy of the key would get."""
    from paddle_tpu.parallel import functional_call

    cfg = tiny_cfg()
    d = DW.dims(cfg)
    layer, lw, rs = _attention_layer(DW.program_config(cfg), seed=6)
    names = [n for n, _ in layer.named_parameters()]
    x = jnp.asarray(rs.randn(2, 128, d["h"]), jnp.float32)
    at = names.index("w_kva")

    def program(w_kva):
        leaves = [w_kva if n == "w_kva" else lw[n] for n in names]
        return jnp.sum(jnp.sin(functional_call(layer, leaves, (x,))._value))

    def reference(w_kva):
        return jnp.sum(jnp.sin(jax.vmap(lambda r: DR.mla_layer(
            r, dict(lw, w_kva=w_kva), d, cfg["rms_norm_eps"], DR.R.mm_f32))(x)))

    with force_interpret():
        got = jax.grad(program)(lw["w_kva"])
    want = jax.grad(reference)(lw["w_kva"])
    assert names[at] == "w_kva"
    _close(got[:, d["latent"]:], want[:, d["latent"]:], 5e-5, "rope columns of w_kva")

    def per_head(keys):
        """One row's loss with a key of its own for each head, [S, H, rope]."""
        y = DR.R.rmsnorm(x[0], lw["input_norm"], cfg["rms_norm_eps"])
        q = DR.R.mm_f32(y, lw["wq"]).reshape(128, d["heads"], -1)
        kva = DR.R.mm_f32(y, lw["w_kva"])
        kv = DR.R.mm_f32(DR.R.rmsnorm(kva[:, :d["latent"]], lw["kv_norm"], cfg["rms_norm_eps"]),
                         lw["w_kvb"]).reshape(128, d["heads"], -1)
        q = jnp.concatenate([q[..., :d["nope"]], DR.rotate(q[..., d["nope"]:], d["theta"])], -1)
        k = jnp.concatenate([kv[..., :d["nope"]], keys], -1)
        o = DR.attention(q, k, kv[..., d["nope"]:], block=64)
        return jnp.sum(jnp.sin(x[0] + DR.R.mm_f32(o.reshape(128, -1), lw["wo"])))

    y = DR.R.rmsnorm(x[0], lw["input_norm"], cfg["rms_norm_eps"])
    k_r = DR.rotate(DR.R.mm_f32(y, lw["w_kva"])[:, None, d["latent"]:], d["theta"])
    g_heads = jax.grad(per_head)(jnp.broadcast_to(k_r, (128, d["heads"], d["rope"])))
    g_shared = jax.grad(lambda k: per_head(jnp.broadcast_to(k, (128, d["heads"], d["rope"]))))(k_r)
    _close(g_shared[:, 0], g_heads.sum(axis=1), 1e-5, "sum over heads")
    assert float(jnp.abs(g_heads[:, 0] - g_heads[:, 1]).max()) > 0.01 * float(jnp.abs(g_heads).max())


def test_what_a_layer_keeps_for_its_backward_does_not_change_what_it_computes():
    """`keep_qkv=False` (this family's stack: flash's output and statistics
    kept, q, k and v built again) against `keep_qkv=True` (Kimi-Linear's one
    layer in four): the same output and the same gradients, by the input and
    every leaf; and the model builds its layers the first way."""
    from paddle_tpu.models.kimi_linear import LatentAttention
    from paddle_tpu.parallel import functional_call

    cfg = DW.program_config(tiny_cfg())
    kept, lw, rs = _attention_layer(cfg, seed=8)
    again = LatentAttention(cfg, cfg.rope_theta, keep_qkv=False)
    assert kept.keep_qkv and not again.keep_qkv
    assert not any(l.mixer.keep_qkv for l in DeepseekV3ForCausalLM(cfg).model.layers)
    names = [n for n, _ in kept.named_parameters()]
    leaves = [lw[n] for n in names]
    x = jnp.asarray(rs.randn(2, 128, cfg.hidden_size), jnp.float32)

    def both(layer):
        return jax.value_and_grad(
            lambda x, *w: jnp.sum(jnp.sin(functional_call(layer, w, (x,))._value)),
            argnums=tuple(range(len(leaves) + 1)))(x, *leaves)

    with force_interpret():
        (l0, g0), (l1, g1) = both(kept), both(again)
    assert float(l0) == float(l1)
    for name, a, b in zip(("x", *names), g0, g1):
        _close(a, b, 1e-6, name)


def test_kimi_linears_layer_is_bit_equal_to_what_it_was_before_the_class_could_rotate():
    """The NoPE layer as the parent of this change wrote it (query, latent,
    the carried key broadcast and concatenated; no rotation), forward and
    backward on a seed, against the shared class under Kimi-Linear's config."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.kimi_linear import rms_norm
    from paddle_tpu.parallel import functional_call

    cfg = kimi_linear_tiny_config(recompute=False)
    assert cfg.mla_use_nope
    layer, lw, rs = _attention_layer(cfg, seed=9)
    names = [n for n, _ in layer.named_parameters()]
    leaves = [lw[n] for n in names]
    x = jnp.asarray(rs.randn(2, 64, 64), jnp.float32)
    heads, nope, rope, vd, rank, eps = 2, 32, 16, 32, 32, cfg.rms_norm_eps

    def before(x, norm, wq, wkva, kvnorm, wkvb, wo):
        b, t, _ = x.shape
        y = rms_norm(x, norm, eps)
        q = (y @ wq).reshape(b, t, heads, nope + rope)
        kva = y @ wkva
        kv = (rms_norm(kva[..., :rank], kvnorm, eps) @ wkvb).reshape(b, t, heads, nope + vd)
        k_r = jnp.broadcast_to(kva[:, :, None, rank:], (b, t, heads, rope))
        k, v = jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]
        o = F.scaled_dot_product_attention(Tensor(q), Tensor(k), Tensor(v),
                                           is_causal=True)._value
        return x + o.reshape(b, t, -1) @ wo

    def now(x, *w):
        return functional_call(layer, w, (x,))._value

    def both(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                  argnums=tuple(range(7)))(x, *leaves)

    (l0, g0), (l1, g1) = both(before), both(now)
    np.testing.assert_array_equal(np.asarray(before(x, *leaves)), np.asarray(now(x, *leaves)))
    assert float(l0) == float(l1)
    for name, a, b in zip(("x", *names), g0, g1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


# ---------------------------------------------------------------------------
# the router: six of 64 by s + b, weights 2.446 s / sum
# ---------------------------------------------------------------------------

def test_router_chooses_six_by_s_plus_b_and_weighs_by_scaled_s_over_the_sum():
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import _route
    from paddle_tpu.models.deepseek_v3 import ExpertMLP

    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(9, 64), jnp.float32)
    bias = jnp.asarray(rs.randn(64) * 0.5, jnp.float32)
    gate = ExpertMLP(deepseek_v3_tiny_config(router_experts=64)).moe.gate
    routing = dict(gate.routing_config(True))
    assert routing["renorm_eps"] == 1e-20 and routing["routed_scale"] == 2.446
    topv, topi, _ = _route(logits, None, k=6, routing=gate.routing_config(True), bias=bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    for row in range(9):
        want = np.argsort(-(s[row] + np.asarray(bias)))[:6]
        assert set(np.asarray(topi[row])) == set(want)
        assert set(want) != set(np.argsort(-s[row])[:6]) or row > 0   # the bias chooses
        ws = s[row][np.asarray(topi[row])]
        np.testing.assert_allclose(np.asarray(topv[row]), 2.446 * ws / ws.sum(), rtol=2e-6)
    w_ref, idx = DR.route(logits, bias, 6, 2.446)
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(topi)))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w_ref), np.asarray(topi), 1),
                               np.asarray(topv), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(w_ref).sum(-1), 2.446, rtol=1e-6)


# ---------------------------------------------------------------------------
# the expert layer: 8 of 64 held, six a token, two shared experts
# ---------------------------------------------------------------------------

def _moe_leaves(cfg, seed=0):
    d = DW.dims(cfg)
    rs = np.random.RandomState(seed)
    h, e, sh = d["h"], d["expert"], d["shared"]
    mk = lambda *s: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)       # noqa: E731
    return {"post_norm": jnp.ones((h,)), "w_gate": mk(d["experts"], h, e),
            "w_up": mk(d["experts"], h, e), "w_down": mk(d["experts"], e, h),
            "shared_gate": mk(h, sh), "shared_up": mk(h, sh), "shared_down": mk(sh, h),
            "router": mk(h, d["experts"])}


def _program_layer(cfg, full, first, bias):
    """The model's own expert block holding experts first .. first + held - 1
    (its HeldExpertsMoE as `ExpertMLP` builds it, rows of 8 a block)."""
    from paddle_tpu.models.deepseek_v3 import ExpertMLP

    d = DW.dims(cfg)
    layer = ExpertMLP(DW.program_config(dict(cfg, first_held_expert=first))).moe
    layer.block_rows = 8
    assert layer.held_experts == (first, first + d["held"]) and layer.top_k == 6
    held = slice(first, first + d["held"])
    for p, v in ((layer.w_gate, full["w_gate"][held]), (layer.w_up, full["w_up"][held]),
                 (layer.w_down, full["w_down"][held]), (layer.shared_gate, full["shared_gate"]),
                 (layer.shared_up, full["shared_up"]), (layer.shared_down, full["shared_down"]),
                 (layer.gate.gate_weight, full["router"])):
        assert tuple(p.shape) == tuple(v.shape)
        p._set_value(v)
    layer.gate.e_score_correction_bias._set_value(jnp.asarray(bias, jnp.float32))
    return layer


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE share test: first_held_expert 0, 8, .., 56; what every chip
    computes alike (the two shared experts, one SwiGLU of twice the width)
    counted once; against the uncut reference's layer of 64 experts."""
    cfg = tiny_cfg(router_experts=64, n_routed_experts=8)
    d = DW.dims(cfg)
    assert (d["held"], d["experts"], d["top_k"], d["shared"]) == (8, 64, 6, 2 * d["expert"])
    full = _moe_leaves(cfg)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(48, d["h"]), jnp.float32)
    bias = jnp.asarray(rs.randn(d["experts"]) * 0.1, jnp.float32)
    whole = dict(cfg, n_routed_experts=64)
    want = DR.moe_layer(x, full, DW.dims(whole), whole, DR.R.mm_f32, bias=bias)[0] - x
    y = DR.R.rmsnorm(x, full["post_norm"], cfg["rms_norm_eps"])
    shared = DR.swiglu(y, full["shared_gate"], full["shared_up"], full["shared_down"],
                       DR.R.mm_f32)
    assert float(jnp.abs(shared).max()) > 0.1 * float(jnp.abs(want).max())
    total, slots = 0.0, 0.0
    with force_interpret():
        for share in range(8):
            layer = _program_layer(cfg, full, 8 * share, bias)
            total = total + layer(paddle.to_tensor(np.asarray(y)))._value
            stats = np.asarray(layer.step_stats._value)
            slots += stats[0]
            assert stats[3] == 0.0                      # nothing dropped
    assert slots == 48 * 6                              # every pair lands on one share
    _close(total - 7 * shared, want, 2e-5, "sum of the shares, the shared experts once")


def test_pairs_past_the_rows_laid_out_are_counted_at_six_a_token():
    """`moe_dropped` CAN fail at k = 6: 8 of 64 held lay out four shares of a
    balanced router, half the pairs; a bias that sends every token's six to
    the held experts lands all of them here and the half past the rows is
    counted."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    cfg = tiny_cfg(router_experts=64, n_routed_experts=8)
    d = DW.dims(cfg)
    bias = np.zeros(d["experts"])
    bias[:d["held"]] = 5.0
    layer = _program_layer(cfg, _moe_leaves(cfg, seed=2), 0, bias)
    x = np.random.RandomState(3).randn(64, d["h"]).astype(np.float32)
    with force_interpret():
        layer(paddle.to_tensor(x))
    stats = np.asarray(layer.step_stats._value)
    pairs = 64 * 6
    rows, _ = held_rows(pairs, d["held"], d["experts"], 8)
    assert rows == pairs // 2
    assert stats[0] == pairs and stats[3] == pairs - rows == float(layer.tokens_dropped._value)


def test_the_resolution_names_each_products_column_tiles():
    """`last_resolution("held_experts").derived["col_tiles"]`: the column tiles
    the forward, dx and dw of the gate / up and the down products cut into,
    as the kernels cut them (one each at these widths)."""
    from paddle_tpu.ops.pallas.grouped_matmul import col_tiles
    from paddle_tpu.tuning.blocks import last_resolution

    cfg = tiny_cfg(router_experts=64, n_routed_experts=8)
    d = DW.dims(cfg)
    layer = _program_layer(cfg, _moe_leaves(cfg), 0, np.zeros(d["experts"]))
    x = np.random.RandomState(4).randn(16, d["h"]).astype(np.float32)
    with force_interpret():
        layer(paddle.to_tensor(x))
    tiles = last_resolution("held_experts").derived["col_tiles"]
    f32 = jnp.float32
    assert tiles == {"gate_up": col_tiles(8, d["h"], d["expert"], f32, f32),
                     "down": col_tiles(8, d["expert"], d["h"], f32, f32)}
    assert tiles["gate_up"] == {"fwd": 1, "dx": 1, "dw": 1}


def test_held_rows_of_the_cell():
    """6 of 64 a token, 8 held: 18,432 pairs land here a step and layer with
    a balanced router (2,304 a held expert) and four times that is laid out."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    pairs = 3 * 8192 * 6
    rows, bm = held_rows(pairs, 8, 64)
    assert pairs * 8 // 64 == 18432 == 8 * 2304
    assert rows == 4 * 18432 == 3 * 3 * 8192 and rows % bm == 0


# ---------------------------------------------------------------------------
# the model: latent attention in every layer, through CompiledTrainStep
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Two AdamW steps of the program (kernels interpreted) and of the
    reference from the same seed."""
    from paddle_tpu.parallel import CompiledTrainStep

    cfg, seed, lr = tiny_cfg(router_bias_update_rate=0.01), 11, 3e-3
    rs = np.random.RandomState(5)
    batches = rs.randint(0, cfg["vocab_size"], (2, 2, 129)).astype(np.int32)
    with force_interpret():
        model = DW.seeded_model(cfg, seed)
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
                # a frozen leaf (router, bias) keeps no moments
                first_m = [np.asarray(st["m"]) if st else np.zeros(v.shape)
                           for st, v in zip(step._opt_states, step._param_vals)]
        step.drain()
        params = [np.asarray(v) for v in step._param_vals]
        counters = step.host_counters()
    pairs = [(b[:, :-1], b[:, 1:]) for b in batches]
    ref = DR.train_steps(cfg, seed, pairs, lr, param_dtype="float32")
    specs = DW.leaf_specs(cfg)
    start = [np.asarray(x) for x in DW.W.make_all(seed, specs, "float32")]
    frozen = DW.frozen(specs)
    return {"cfg": cfg, "seed": seed, "pairs": pairs, "specs": specs, "start": start,
            "losses": losses, "grads": [m / 0.1 for m in first_m],
            "change": [0.0 if f else np.sqrt(np.sum((p - s) ** 2))
                       for p, s, f in zip(params, start, frozen)],
            "biases": [p for p, (name, *_) in zip(params, specs)
                       if name.endswith("router_bias")],
            "routers": [(p, s) for p, s, (name, *_) in zip(params, start, specs)
                        if name.endswith(".router")],
            "ref": ref, "counters": counters}


def test_model_is_latent_attention_in_every_layer_with_an_untied_head():
    cfg = tiny_cfg()
    assert DW.layer_kinds(cfg) == [("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    model = DeepseekV3ForCausalLM(DW.program_config(cfg))
    assert [tuple(p.shape) for p in model.parameters()] == [s[1] for s in DW.leaf_specs(cfg)]
    names = [n for n, _ in model.named_parameters()]
    assert names[0] == "model.embed_tokens.weight" and names[-1] == "lm_head.weight"
    assert sum(n.endswith("mixer.w_kva") for n in names) == 3
    assert sum(n.endswith("shared_gate") for n in names) == 2
    logits = model(paddle.to_tensor(np.zeros((1, 8), np.int32)))
    assert tuple(logits.shape) == (1, 8, cfg["vocab_size"])


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
    want = trained["ref"]["biases"]
    assert len(trained["biases"]) == len(want) == 2
    for got, ref in zip(trained["biases"], want):
        assert got.dtype == np.float32 and np.abs(got).max() > 0
        np.testing.assert_allclose(got, ref, atol=1e-7)
    for now, start in trained["routers"]:
        np.testing.assert_array_equal(now, start)


def test_step_counters_carry_the_expert_load(trained):
    moe = trained["counters"]["moe"]
    assert moe["steps"] == 2 and moe["dropped"] == 0.0
    pairs = 2 * 128 * 6 * 2                    # rows x tokens x k x expert layers
    assert 0 < moe["routed_slots"] / 2 <= pairs
    assert moe["max_expert_load"] >= moe["mean_expert_load"] > 0
