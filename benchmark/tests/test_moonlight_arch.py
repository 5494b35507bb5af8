"""The DeepSeek-V3-family files the kind `train_arch` takes (weights,
reference, roofline, readers, the four metric files), on the CPU at toy sizes
with the kernels interpreted, and the readers on a small recorded trace of
the cell `train_moonlight_seq8k`. Nothing here is a measurement."""
import dataclasses
import gzip
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, named, reduce
from benchmark.arch.deepseek_v3 import readers
from benchmark.arch.deepseek_v3 import reference as DR
from benchmark.arch.deepseek_v3 import roofline as DRoof
from benchmark.arch.deepseek_v3 import weights as DW
from benchmark.kinds import train_arch

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train_moonlight_seq8k"
OURS = ["step_mfu.train_moonlight", "moonlight_mla_flash_roofline",
        "moonlight_expert_gmm_roofline", "moonlight_named_kernels_step_share_pct"]
SEED = 2147485001


def tiny_cell(**limits) -> dict:
    from paddle_tpu.models import deepseek_v3_tiny_config

    model = dataclasses.asdict(deepseek_v3_tiny_config(router_bias_update_rate=0.01))
    model.update(dtype="bfloat16", arch="deepseek_v3")
    return {"name": "tiny", "kind": "train_arch", "chips": 1, "mesh": None, "model": model,
            "mix": {"family": "token_stream", "rows": 2, "seq_len": 96},
            "train": {"learning_rate": 3e-3, "warmup_steps": 4, "weight_decay": 0.01, "batches": 8,
                      "check_steps": 2, "log_every": 2},
            "trace_s": 0.2,
            # the program over three seeds here: 7e-6 to 2e-5, 2.9e-3 to 4.4e-3, 1.6e-3
            # to 3.3e-3; the float8 control: 1.3e-4 to 2.1e-4, 0.014 to 0.019, 4.2e-3 to
            # 5.3e-3
            "limits": dict({"loss_gap": 6e-5, "grad_gap": 0.009, "change_gap": 0.0038,
                            "moe_dropped": 0, "compiles_in_window": 0,
                            "last_loss_finite": 0}, **limits)}


@pytest.fixture(scope="module")
def ran():
    import jax

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "used": jax.devices()[:1]}
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=0)
    with harness.interpret_kernels(True):
        out = train_arch.run(cell, args, device, harness.CompileMeter(), 0.0)
    return cell, out


def test_cell_files_resolve():
    cell = harness.load_cell(CELL)
    assert cell["kind"] == "train_arch" and cell["model"]["arch"] == "deepseek_v3"
    assert (cell["chips"], cell["traffic"], cell["mix"]["rows"], cell["mix"]["seq_len"]) == (
        1, "seq8k_x3", 3, 8192)
    for part in ("weights", "reference", "roofline"):
        assert train_arch.arch_of(cell, part).__name__.endswith(f"deepseek_v3.{part}")
    manifest = harness.load_manifest()
    listed = [m["name"] for m in harness.cell_metrics(manifest, CELL, "per_layer")]
    assert set(OURS) <= set(listed)
    assert {"ce_stats_roofline", "moe_routed_slots_per_step", "moe_expert_load_max_over_mean",
            "compile_s", "device_idle_pct.train", "train_host_ms_per_step",
            "train_input_ms_per_step", "compile_cache_misses"} <= set(listed)
    for name in OURS:
        assert os.path.exists(os.path.join(harness.HERE, "metrics", f"{name}.py"))
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "train_tokens_per_s_per_chip")["workloads"]


def test_the_tiny_cell_is_correct_against_its_reference(ran):
    _, out = ran
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["checks"]["moe_dropped"]["value"] == 0.0
    assert set(out["end_to_end"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert out["run"]["resolutions"]["held_experts"]["rows"] == 2 * 96 * 6      # every pair
    assert "kda" not in out["run"]["resolutions"]


def test_the_router_is_live_and_the_window_counts_the_load(ran):
    cell, out = ran
    loads = out["run"]["ref"]["loads"]
    assert len(loads) == 2 and loads[0].shape == (2, 16)
    for load in loads:
        assert (load.sum(axis=1) == 2 * 96 * 6).all()          # k experts a token
        assert ((load > 0).sum(axis=1) > 8).all() and load.max() <= 2 * 96
    moe = out["run"]["moe"]
    assert moe["steps"] == out["attempted"]
    assert 0 < moe["routed_slots"] / moe["steps"] <= 2 * 96 * 6 * 2
    # the counters alone carry the two readers that need no trace
    run = dict(out["run"], device={"kind": "TPU v5 lite"})
    got = harness.read_per_layer(["moe_routed_slots_per_step", "step_mfu.train_moonlight"], run)
    assert got["moe_routed_slots_per_step"] > 0 and got["step_mfu.train_moonlight"] > 0
    assert readers.pairs_per_token(run) == pytest.approx(
        moe["routed_slots"] / moe["steps"] / (2 * 96) / 2)


@pytest.mark.parametrize("fault", ["half_batch", "other_experts", "float8"])
def test_the_faults_and_the_control_fail(ran, fault):
    """Half of the batch left out, experts 4-7 computed in place of 0-3, and
    the reference with float8 projections: each must read over a limit."""
    from benchmark import check
    from benchmark import reference as R

    cell, out = ran
    ref, batches = out["run"]["ref"], [(b[:, :-1], b[:, 1:]) for b in out["run"]["check_batches"]]
    kw = {"half_batch": {"rows": slice(0, 1)}, "other_experts": {"first": 4},
          "float8": {"mm": R.mm_fp8}}[fault]
    bad = DR.train_steps(cell["model"], SEED, batches, 3e-3, param_dtype="bfloat16",
                         warmup_steps=cell["train"]["warmup_steps"], **kw)
    numbers = check.train_numbers(bad, ref)
    judged = check.judge(numbers, {k: cell["limits"][k] for k in numbers})
    print(fault, numbers)
    assert not all(c["ok"] for c in judged.values()), numbers


def test_the_rotation_left_out_is_another_reference(ran):
    """The fault this architecture adds. At toy widths with weights of std
    0.02 attention is all but uniform and the three gaps (norms of leaves,
    which a rotation keeps) stay inside the limits: what tells it here is the
    layer test in tests/test_deepseek_v3.py; on the chip, PERF.md section 6."""
    cell, out = ran
    batches = [(b[:, :-1], b[:, 1:]) for b in out["run"]["check_batches"]]
    kw = dict(param_dtype="bfloat16", warmup_steps=cell["train"]["warmup_steps"])
    bad = DR.train_steps(cell["model"], SEED, batches, 3e-3, rotated=False, **kw)
    assert bad["losses"] != out["run"]["ref"]["losses"]
    assert np.abs(bad["grad_norms"] - out["run"]["ref"]["grad_norms"]).max() > 0


def test_attention_a_block_at_a_time_is_causal_attention():
    """The whole masked softmax written out, against the reference's blocks of
    queries, forward and backward."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    s, h = 64, 2
    q, k, v = (jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32) for d in (24, 24, 16))

    def plain(q, k, v):
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=DR.HI) * 24 ** -0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision=DR.HI)

    ours = lambda q, k, v: DR.attention(q, k, v, block=8)      # noqa: E731
    np.testing.assert_allclose(ours(q, k, v), plain(q, k, v), atol=2e-6)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))      # noqa: E731
    for got, want in zip(jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v),
                         jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=5e-6)


def test_parameters_of_the_published_configuration_by_hand():
    """The issue's arithmetic at the published widths: latent attention
    6.29 + 1.18 + 2.10 + 4.19 = 13.76 M, layer 0 with its 69.2 M feed-forward
    83.0 M, an expert layer 31.2 M outside its experts, an expert 8.65 M,
    669 M held (9.36 GB at 14 bytes)."""
    cfg = harness.load_cell(CELL)["model"]
    parts = DRoof.parameters(cfg)
    norms = 2048 + 512
    assert parts["attention"] == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048 + norms
    assert round(parts["attention"] / 1e6, 2) == 13.77          # 13.76 M of matrices + norms
    assert parts["dense_layer"] == parts["attention"] + 3 * 2048 * 11264 + 2048
    assert round(parts["dense_layer"] / 1e6, 1) == 83.0
    assert parts["expert"] == 3 * 2048 * 1408 and round(parts["expert"] / 1e6, 2) == 8.65
    outside = parts["attention"] + 2048 * 64 + 64 + 3 * 2048 * 2816 + 2048
    assert parts["expert_layer_outside_experts"] == outside and round(outside / 1e6, 1) == 31.2
    total = parts["dense_layer"] + 5 * (outside + 8 * parts["expert"]) + 2 * 20480 * 2048 + 2048
    assert parts["total"] == total and round(total / 1e6) == 669
    assert round(total * 14 / 1e9, 2) == 9.36


def test_required_work_of_the_published_configuration():
    """313 M parameters a token is multiplied by (the held experts at 0.75
    pairs a token and layer), 1.88 GFLOP of matrices and 0.755 GFLOP of causal
    attention at 16 x (192 + 128) over six layers: 29% of the total."""
    cfg = harness.load_cell(CELL)["model"]
    weights = DRoof.matmul_params(cfg)
    mats = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert weights == 6 * mats + 3 * 2048 * 11264 + 2048 * 20480 + 5 * (
        2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408)
    assert round(weights / 1e6) == 313
    total = DRoof.train_flops_per_token(cfg, 8192)
    attn = 3 * 6 * 2 * 16 * (192 + 128) * 4096.5
    assert total == pytest.approx(6 * weights + attn)
    assert round(6 * weights / 1e9, 2) == 1.88 and round(attn / 1e9, 3) == 0.755
    assert round(100 * attn / total) == 29
    assert (DRoof.n_layers(cfg, "mla"), DRoof.n_layers(cfg, "dense"),
            DRoof.n_layers(cfg, "moe"), DRoof.n_layers(cfg, "kda")) == (6, 1, 5, 0)
    # the pairs the program counts move the experts' share and nothing else
    assert DRoof.matmul_params(cfg, 1.0) - weights == pytest.approx(5 * 0.25 * 3 * 2048 * 1408)
    flops, bytes_ = DRoof.expert_gmm(cfg, 18432)
    assert flops == 9 * 2 * 18432 * 2048 * 1408 and flops / bytes_ > 240   # compute-bound
    flops, bytes_ = DRoof.flash_fwd(cfg, 3, 8192)
    assert flops == 3 * 2 * 16 * 320 * 8192 * 4096.5
    assert bytes_ == 3 * 8192 * 16 * (2 * 192 + 2 * 128) * 2
    assert DRoof.flash_bwd(cfg, 3, 8192)[0] == 3 * 2 * 16 * (3 * 192 + 2 * 128) * 8192 * 4096.5


def test_configuration_holds_every_published_key():
    """Every number of the catalog row's config under its own key, the three
    cuts with their published values, the deployment and what was assumed."""
    cfg = harness.load_json("configs", "moonlight-16b-a3b.json")
    published = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
                 "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
                 "kv_lora_rank": 512, "max_position_embeddings": 8192,
                 "model_type": "deepseek_v3", "moe_intermediate_size": 1408, "moe_layer_freq": 1,
                 "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2,
                 "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 6,
                 "num_hidden_layers": 27, "num_key_value_heads": 16,
                 "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
                 "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
                 "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
                 "v_head_dim": 128, "vocab_size": 163840}
    assert cfg["reduced"].keys() == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["published"] == value and cfg[key] == cfg["reduced"][key]["run"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 8, 20480)
    assert cfg["router_experts"] == 64 and cfg["first_held_expert"] == 0
    assert cfg["router_bias_update_rate"] == 0.001 and cfg["dtype"] == "bfloat16"
    assert "8 chips" in cfg["deployment"] and "669 M" in cfg["deployment"]
    assert {"rotation", "mla", "router", "seq_aux", "shared_experts", "weights", "optimizer",
            "learning_rate"} <= cfg["assumed"].keys()
    assert "Muon" in cfg["assumed"]["optimizer"] and "1e-20" in cfg["assumed"]["router"]
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "moonlight-16b-a3b")
    assert set(entry["reduced"]) == cfg["reduced"].keys() and entry["source"] == cfg["source"]


def test_readers_and_roofline_import_no_model_code():
    """The check runs these files over the parent's program, which has no
    `paddle_tpu.models.deepseek_v3`: nothing a reader imports may need it."""
    import subprocess
    import sys

    code = ("import sys; import benchmark.arch.deepseek_v3.readers, "
            "benchmark.arch.deepseek_v3.roofline, benchmark.arch.deepseek_v3.weights; "
            "bad = [m for m in sys.modules if m.startswith('paddle_tpu')]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=harness.ROOT,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


# ---------------------------------------------------------------------------
# the readers, on a small recorded trace of this cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    path = os.path.join(HERE, "data", "moonlight_trace_v5e.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace of the cell yet")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    reduced = reduce.reduce_events(recorded, 1)
    reduced["events"] = recorded
    return dict(recorded["run"], cell=harness.load_cell(CELL), device={"kind": "TPU v5 lite"},
                trace=reduced)


@pytest.mark.parametrize("metric", OURS + ["ce_stats_roofline"])
def test_trace_readers_find_their_kernels(traced_run, metric):
    value = harness.read_per_layer([metric], traced_run)[metric]
    assert 0.0 < value <= 100.0


def test_roofline_readers_count_the_kernels_own_events(traced_run):
    """One `flash_dq` a layer and step (six a step), three `grouped_matmul_dw`
    an expert layer and step (fifteen a step); doubling every kernel event and
    its time leaves a share where it was (no count of programs in the slice
    enters)."""
    trace = traced_run["trace"]
    dq = readers.events(trace, "flash_dq")
    assert dq > 0 and readers.events(trace, "grouped_matmul_dw") == pytest.approx(15 * dq / 6)
    names = ["moonlight_mla_flash_roofline", "moonlight_expert_gmm_roofline"]
    before = harness.read_per_layer(names, traced_run)
    twice = dict(traced_run, trace=dict(
        trace, op_s={k: 2 * v for k, v in trace["op_s"].items()},
        op_n={k: 2 * v for k, v in trace["op_n"].items()}))
    assert harness.read_per_layer(names, twice) == pytest.approx(before)
    assert named.kernel_seconds(trace, "ce_stats") > 0


@pytest.mark.parametrize("other", ["train_kimilinear_seq8k", "train_lfm2moe_seq8k"])
def test_readers_return_nothing_on_another_architectures_run(other):
    """Every new reader None, never raising: on a Kimi-Linear and an LFM2-MoE
    run with their counters and a trace that HAS the kernels these readers
    look for, and on a run without a trace."""
    ops = [[f"%{k}.1 = bf16[8] custom-call(), custom_call_target={reduce.PALLAS_CALL}", 0.0, 10.0]
           for k in ("flash_fwd", "flash_dq", "flash_dkv", "grouped_matmul",
                     "grouped_matmul_dw", "ce_stats")]
    trace = reduce.reduce_events({"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
                                  "host": []}, 1)
    assert named.kernel_seconds(trace, "flash_dq") > 0
    run = {"cell": harness.load_cell(other), "device": {"kind": "TPU v5 lite"}, "trace": trace,
           "trace_window_s": 1.0, "tokens_per_s_per_chip": 1.0, "tokens_per_step": 16384,
           "moe": {"steps": 2, "routed_slots": 8.0}, "moe_slice": {"steps": 1, "routed_slots": 4.0}}
    assert harness.read_per_layer(OURS, run) == {}
    assert harness.read_per_layer(OURS, dict(run, trace=None)) == {}


def test_readers_return_nothing_without_their_kernels():
    """This architecture's own run without the kernels, the counters or a
    trace: every trace reader None; the host-clock share needs no trace."""
    empty = reduce.reduce_events({"devices": {"/device:TPU:0": {"ops": [
        ["%fusion.1 = bf16[8] fusion()", 0.0, 10.0]], "modules": []}}, "host": []}, 1)
    run = {"cell": harness.load_cell(CELL), "device": {"kind": "TPU v5 lite"},
           "trace": empty, "trace_window_s": 1.0}
    assert harness.read_per_layer(OURS, run) == {}
    assert harness.read_per_layer(OURS, dict(run, trace=None)) == {}
    assert harness.read_per_layer(OURS, {"device": {"kind": "TPU v5 lite"}}) == {}
    plain = dict(run, trace=None, tokens_per_s_per_chip=25000.0, tokens_per_step=24576)
    got = harness.read_per_layer(OURS, plain)
    assert set(got) == {"step_mfu.train_moonlight"} and 30 < got["step_mfu.train_moonlight"] < 40
