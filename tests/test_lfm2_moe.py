"""LFM2-MoE against its plain reference (benchmark/arch/lfm2_moe/reference.py:
float32, the convolution as shifted taps and token by token, attention
materialised, experts by a plain loop, the head the embedding transposed), on
seeded weights at toy sizes with the Pallas kernels interpreted: the gated
short convolution, q/k norm before RoPE and flash at width 64, the router
with its bias and its epsilon, the expert layer without a shared expert and
its share test, the tied leaf, and the whole five-layer model (all three
kinds of layer) through `CompiledTrainStep`, leaf by leaf.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.arch.lfm2_moe import reference as LR
from benchmark.arch.lfm2_moe import weights as LW
from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM, lfm2_moe_tiny_config
from paddle_tpu.ops.pallas.flash_attention import (flash_attention_bshd,
                                                   force_interpret)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**kw) -> dict:
    cfg = dataclasses.asdict(lfm2_moe_tiny_config(**kw))
    cfg["dtype"] = "float32"
    return cfg


def _close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
    assert err <= tol, f"{what}: {err:.3e} > {tol}"


# ---------------------------------------------------------------------------
# the configuration: the published pattern and the cut
# ---------------------------------------------------------------------------

def test_published_layer_types_and_the_cut():
    """30 conv and 10 full_attention, layers 0 and 1 dense; the benchmark's
    configuration is published layers 1-5 with every width as published."""
    whole = dataclasses.asdict(Lfm2MoeConfig())
    kinds = LW.layer_kinds(whole)
    assert [m for m, _ in kinds].count("conv") == 30
    assert [m for m, _ in kinds].count("full_attention") == 10
    assert [f for _, f in kinds] == ["dense"] * 2 + ["moe"] * 38
    assert Lfm2MoeConfig().layer_kinds() == kinds
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as f:
        cut = json.load(f)
    assert cut["layer_types"] == whole["layer_types"] and cut["num_dense_layers"] == 2
    assert LW.layer_kinds(cut) == kinds[1:6] == [
        ("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe")]
    assert LW.program_config(cut).layer_kinds() == kinds[1:6]
    for key, width in (("hidden_size", 2048), ("intermediate_size", 11776),
                       ("moe_intermediate_size", 1536), ("num_attention_heads", 32),
                       ("num_key_value_heads", 8), ("num_experts_per_tok", 4),
                       ("router_experts", 64), ("conv_L_cache", 3)):
        assert cut[key] == width, key
    assert cut["rope_parameters"]["rope_theta"] == 1000000
    params = sum(int(np.prod(s[1])) for s in LW.leaf_specs(cut))
    assert round(params / 1e6, 1) == 469.3


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

def _conv_layer(cfg, seed=0):
    from paddle_tpu.models.lfm2_moe import ShortConv

    rs = np.random.RandomState(seed)
    layer = ShortConv(LW.program_config(cfg))
    lw = {}
    for name, p in layer.named_parameters():
        lw[name] = jnp.asarray(p.numpy() + 0.05 * rs.randn(*p.shape), jnp.float32)
        p._set_value(lw[name])
    return layer, lw


def test_the_convolution_is_a_recurrence_over_two_past_values():
    """The layer equals the reference's, and the reference's shifted taps
    equal a token-by-token walk whose state is the last two B * u."""
    cfg = tiny_cfg()
    d = LW.dims(cfg)
    layer, lw = _conv_layer(cfg)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 40, d["h"]), jnp.float32)
    got = layer(paddle.to_tensor(np.asarray(x)))._value
    want = jax.vmap(lambda r: LR.conv_layer(r, lw, d, cfg["norm_eps"], LR.R.mm_f32))(x)
    _close(got, want, 2e-5, "layer")
    y = LR.R.rmsnorm(x[0], lw["operator_norm"], cfg["norm_eps"])
    b, c, u = jnp.split(LR.R.mm_f32(y, lw["w_in"]), 3, axis=-1)
    walked = x[0] + LR.R.mm_f32(c * LR.conv_recurrence(b * u, lw["conv"]), lw["w_out"])
    _close(walked, want[0], 1e-6, "token by token")


def test_the_convolution_is_blind_to_the_future():
    cfg = tiny_cfg()
    layer, _ = _conv_layer(cfg, seed=2)
    rs = np.random.RandomState(3)
    x = rs.randn(1, 32, cfg["hidden_size"]).astype(np.float32)
    later = x.copy()
    later[:, 20:] = rs.randn(1, 12, cfg["hidden_size"])
    a, b = (np.asarray(layer(paddle.to_tensor(v))._value) for v in (x, later))
    np.testing.assert_array_equal(a[:, :20], b[:, :20])
    assert np.abs(a[:, 20:] - b[:, 20:]).max() > 0.1


# ---------------------------------------------------------------------------
# attention: q/k norm before RoPE, flash at width 64, 4 query heads a key head
# ---------------------------------------------------------------------------

def test_flash_at_width_64_grouped_4_to_1_matches_materialised_attention():
    rs = np.random.RandomState(0)
    s = 256
    q = jnp.asarray(rs.randn(2, s, 8, 64), jnp.float32)
    k, v = (jnp.asarray(rs.randn(2, s, 2, 64), jnp.float32) for _ in range(2))

    def ref(q, k, v):
        return jax.vmap(lambda a, b, c: LR.R.attention(a, b, c, block=64))(q, k, v)

    def vg(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))

    with force_interpret():
        l1, g1 = vg(lambda *a: flash_attention_bshd(*a, causal=True))(q, k, v)
    l0, g0 = vg(ref)(q, k, v)
    _close(l1, l0, 1e-5, "loss")
    for name, a, b in zip(("dq", "dk", "dv"), g1, g0):
        _close(a, b, 2e-5, name)


def test_attention_layer_norms_q_and_k_before_rope():
    """The layer against the reference's, with norm weights off 1 so that a
    norm after the rotation, or none, would show."""
    from paddle_tpu.models.lfm2_moe import Attention

    cfg = tiny_cfg()
    d = LW.dims(cfg)
    rs = np.random.RandomState(4)
    layer = Attention(LW.program_config(cfg))
    lw = {}
    for name, p in layer.named_parameters():
        lw[name] = jnp.asarray(p.numpy() + 0.3 * rs.randn(*p.shape), jnp.float32)
        p._set_value(lw[name])
    x = jnp.asarray(rs.randn(2, 128, d["h"]), jnp.float32)
    with force_interpret():
        got = layer(paddle.to_tensor(np.asarray(x)))._value
    want = jax.vmap(lambda r: LR.attention_layer(r, lw, d, cfg["norm_eps"], LR.R.mm_f32))(x)
    _close(got, want, 2e-5, "layer")
    # the rotation does not commute with a norm whose weights differ a channel
    s, hd = 128, d["hd"]
    q = LR.R.mm_f32(x[0], lw["wq"]).reshape(s, d["heads"], hd)
    before = LR.R.rope(LR.R.rmsnorm(q, lw["q_norm"], 1e-5), jnp.arange(s), d["theta"])
    after = LR.R.rmsnorm(LR.R.rope(q, jnp.arange(s), d["theta"]), lw["q_norm"], 1e-5)
    assert float(jnp.abs(before - after).max()) > 0.1


# ---------------------------------------------------------------------------
# the router: choice by s + b, weights by s over the sum plus 1e-6
# ---------------------------------------------------------------------------

def test_router_chooses_by_s_plus_b_and_weighs_by_s_over_the_sum_plus_eps():
    from paddle_tpu.incubate.distributed.models.moe import SigmoidGate
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import _route

    rs = np.random.RandomState(0)
    # strongly negative scores: the chosen s are small and the epsilon shows
    logits = jnp.asarray(rs.randn(6, 16) - 9.0, jnp.float32)
    bias = jnp.asarray(rs.randn(16), jnp.float32)
    gate = SigmoidGate(8, 16, topk=4, renorm_eps=1e-6)
    routing = gate.routing_config(True)
    assert dict(routing)["renorm_eps"] == 1e-6
    assert dict(SigmoidGate(8, 16, topk=4).routing_config(True))["renorm_eps"] == 0.0
    topv, topi, _ = _route(logits, None, k=4, routing=routing, bias=bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    for row in range(6):
        want = np.argsort(-(s[row] + np.asarray(bias)))[:4]
        assert set(np.asarray(topi[row])) == set(want)
        ws = s[row][np.asarray(topi[row])]
        np.testing.assert_allclose(np.asarray(topv[row]), ws / (ws.sum() + 1e-6), rtol=2e-6)
        assert abs(np.asarray(topv[row]).sum() - 1.0) > 1e-4       # the epsilon is felt
    w_ref, idx = LR.route(logits, bias, 4)
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(topi)))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w_ref), np.asarray(topi), 1),
                               np.asarray(topv), rtol=2e-6)
    # without the epsilon the weights add up to one, as in the Kimi family
    plain, _, _ = _route(logits, None, k=4, bias=bias, routing=(("kind", "sigmoid"),))
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)
    # and an epsilon of zero is the program it was before the key existed
    # (the Kimi-Linear step's lowered text is the parent's: PERF.md section 6)
    traced = [str(jax.make_jaxpr(lambda l, r=r: _route(l, None, k=4, routing=r, bias=bias)[0])(
        logits)) for r in ((("kind", "sigmoid"),), (("kind", "sigmoid"), ("renorm_eps", 0.0)))]
    assert traced[0] == traced[1] != str(jax.make_jaxpr(
        lambda l: _route(l, None, k=4, routing=routing, bias=bias)[0])(logits))


# ---------------------------------------------------------------------------
# the expert layer without a shared expert
# ---------------------------------------------------------------------------

def _moe_leaves(cfg, seed=0):
    d = LW.dims(cfg)
    rs = np.random.RandomState(seed)
    h, e = d["h"], d["expert"]
    mk = lambda *s: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)       # noqa: E731
    return {"ffn_norm": jnp.ones((h,)), "w_gate": mk(d["experts"], h, e),
            "w_up": mk(d["experts"], h, e), "w_down": mk(d["experts"], e, h),
            "router": mk(h, d["experts"])}


def _program_layer(cfg, full, first, bias):
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE

    d = LW.dims(cfg)
    layer = HeldExpertsMoE(d["h"], d["experts"], d["expert"], d["top_k"],
                           held_experts=(first, first + d["held"]), renorm_eps=1e-6,
                           num_shared=0, block_rows=8)
    held = slice(first, first + d["held"])
    for p, v in ((layer.w_gate, full["w_gate"][held]), (layer.w_up, full["w_up"][held]),
                 (layer.w_down, full["w_down"][held]), (layer.gate.gate_weight, full["router"])):
        p._set_value(v)
    layer.gate.e_score_correction_bias._set_value(jnp.asarray(bias, jnp.float32))
    return layer


def test_no_shared_expert_means_no_shared_leaves():
    """`num_shared=0`: three expert leaves, the router's two, nothing of
    width zero for the optimizer to carry."""
    layer = _program_layer(tiny_cfg(), _moe_leaves(tiny_cfg()), 0, np.zeros(16))
    names = [n for n, _ in layer.named_parameters()]
    assert names == ["w_gate", "w_up", "w_down", "gate.gate_weight",
                     "gate.e_score_correction_bias"]
    assert all(int(np.prod(p.shape)) > 0 for p in layer.parameters())


def test_shares_of_all_chips_add_up_to_the_uncut_layer():
    """THE share test: with no shared expert the held parts of the four
    shares simply add up to the uncut reference's layer."""
    cfg = tiny_cfg()
    d = LW.dims(cfg)
    full = _moe_leaves(cfg)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(48, d["h"]), jnp.float32)
    bias = jnp.asarray(rs.randn(d["experts"]) * 0.1, jnp.float32)
    whole = dict(cfg, num_experts=d["experts"], router_experts=d["experts"])
    want = LR.moe_layer(x, full, LW.dims(whole), whole, LR.R.mm_f32, bias=bias)[0] - x
    y = LR.R.rmsnorm(x, full["ffn_norm"], cfg["norm_eps"])
    total, slots = 0.0, 0.0
    with force_interpret():
        for share in range(d["experts"] // d["held"]):
            layer = _program_layer(cfg, full, share * d["held"], bias)
            total = total + layer(paddle.to_tensor(np.asarray(y)))._value
            stats = np.asarray(layer.step_stats._value)
            slots += stats[0]
            assert stats[3] == 0.0                      # nothing dropped
    assert slots == 48 * d["top_k"]                     # every pair lands on one share
    _close(total, want, 2e-5, "sum of the shares")


def test_pairs_past_the_rows_laid_out_are_counted():
    """`moe_dropped` CAN fail: 4 of 32 experts held, four a token, so four
    shares of a balanced router are half the tokens' pairs; a bias that sends
    every token to the held experts lands all of them here and the half past
    the rows laid out is counted."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    cfg = tiny_cfg(router_experts=32)
    d = LW.dims(cfg)
    bias = np.zeros(d["experts"])
    bias[:d["held"]] = 5.0
    layer = _program_layer(cfg, _moe_leaves(cfg, seed=2), 0, bias)
    x = np.random.RandomState(3).randn(64, d["h"]).astype(np.float32)
    with force_interpret():
        layer(paddle.to_tensor(x))
    stats = np.asarray(layer.step_stats._value)
    pairs = 64 * d["top_k"]
    rows, _ = held_rows(pairs, d["held"], d["experts"], 8)
    assert rows == pairs // 2
    assert stats[0] == pairs and stats[3] == pairs - rows == float(layer.tokens_dropped._value)


def test_held_rows_of_the_cell():
    """An eighth of the pairs land here: two rows a token are laid out."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    rows, bm = held_rows(3 * 8192 * 4, 8, 64)
    assert rows == 49152 == 2 * 3 * 8192 and rows % bm == 0


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
        model = LW.seeded_model(cfg, seed)
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
    ref = LR.train_steps(cfg, seed, pairs, lr, param_dtype="float32")
    specs = LW.leaf_specs(cfg)
    start = [np.asarray(x) for x in LW.W.make_all(seed, specs, "float32")]
    frozen = LW.frozen(specs)
    return {"cfg": cfg, "seed": seed, "pairs": pairs, "specs": specs, "start": start,
            "losses": losses, "grads": [m / 0.1 for m in first_m],
            "change": [0.0 if f else np.sqrt(np.sum((p - s) ** 2))
                       for p, s, f in zip(params, start, frozen)],
            "biases": [p for p, (name, *_) in zip(params, specs)
                       if name.endswith("router_bias")],
            "routers": [(p, s) for p, s, (name, *_) in zip(params, start, specs)
                        if name.endswith(".router")],
            "ref": ref, "counters": counters}


def test_model_has_all_three_kinds_of_layer_and_one_tied_leaf():
    cfg = tiny_cfg()
    assert LW.layer_kinds(cfg) == [("conv", "dense"), ("full_attention", "moe"),
                                   ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    model = Lfm2MoeForCausalLM(LW.program_config(cfg))
    assert [tuple(p.shape) for p in model.parameters()] == [s[1] for s in LW.leaf_specs(cfg)]
    names = [n for n, _ in model.named_parameters()]
    assert names[0] == "model.embed_tokens.weight" and not any("head" in n for n in names)
    assert not any("shared" in n for n in names)
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


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(trained):
    """The step's first gradient of the embedding (Adam's `m` / 0.1) against
    the reference's with the two uses apart: what the gather gives plus,
    transposed, what the head's product gives. Neither alone would do."""
    cfg, (ids, labels) = trained["cfg"], trained["pairs"][0]
    leaves = [jnp.asarray(x) for x in trained["start"]]

    def apart(embed, head):
        total = sum(LR.row_loss([embed] + leaves[1:], jnp.asarray(i), jnp.asarray(l), cfg,
                                LR.R.mm_f32, head=head)[0] for i, l in zip(ids, labels))
        return total / ids.size

    g_embed, g_head = jax.grad(apart, argnums=(0, 1))(leaves[0], leaves[0].T)
    got = trained["grads"][0]
    _close(got, g_embed + g_head.T, 2e-4, "sum of both uses")
    scale = float(jnp.abs(g_embed + g_head.T).max())
    assert float(jnp.abs(got - g_embed).max()) > 0.1 * scale
    assert float(jnp.abs(got - g_head.T).max()) > 0.1 * scale


def test_the_step_moves_the_bias_as_the_reference_and_leaves_the_router(trained):
    want = trained["ref"]["biases"]
    assert len(trained["biases"]) == len(want) == 4
    for got, ref in zip(trained["biases"], want):
        assert got.dtype == np.float32 and np.abs(got).max() > 0
        np.testing.assert_allclose(got, ref, atol=1e-7)
    for now, start in trained["routers"]:
        np.testing.assert_array_equal(now, start)


def test_step_counters_carry_the_expert_load(trained):
    moe = trained["counters"]["moe"]
    d = LW.dims(trained["cfg"])
    assert moe["steps"] == 2 and moe["dropped"] == 0.0
    pairs = 2 * 96 * d["top_k"] * 4            # rows x tokens x k x expert layers
    assert 0 < moe["routed_slots"] / 2 <= pairs
    assert moe["max_expert_load"] >= moe["mean_expert_load"] > 0
