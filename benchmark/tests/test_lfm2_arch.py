"""The LFM2-MoE files the kind `train_arch` takes (weights, reference,
roofline, readers, the four metric files), on the CPU at toy sizes with the
kernels interpreted, and the readers on a small recorded trace of the cell
`train_lfm2moe_seq8k`. Nothing here is a measurement."""
import dataclasses
import gzip
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, named, reduce
from benchmark.arch.lfm2_moe import readers
from benchmark.arch.lfm2_moe import reference as LR
from benchmark.arch.lfm2_moe import roofline as LRoof
from benchmark.arch.lfm2_moe import weights as LW
from benchmark.kinds import train_arch

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train_lfm2moe_seq8k"
OURS = ["step_mfu.train_lfm2moe", "lfm2_flash_roofline", "lfm2_expert_gmm_roofline",
        "lfm2_named_kernels_step_share_pct"]


def tiny_cell(**limits) -> dict:
    from paddle_tpu.models import lfm2_moe_tiny_config

    model = dataclasses.asdict(lfm2_moe_tiny_config(router_bias_update_rate=0.01))
    model.update(dtype="bfloat16", arch="lfm2_moe")
    return {"name": "tiny", "kind": "train_arch", "chips": 1, "mesh": None, "model": model,
            "mix": {"family": "token_stream", "rows": 2, "seq_len": 96},
            "train": {"learning_rate": 3e-3, "warmup_steps": 4, "weight_decay": 0.01, "batches": 8,
                      "check_steps": 2, "log_every": 2},
            "trace_s": 0.2,
            # the program over three seeds here: 2.2e-5 to 6.8e-5, 8.2e-3 to 8.9e-3, 1.5e-3
            # to 2.6e-3; the float8 control: 3.5e-4, 0.037, 4.7e-3
            "limits": dict({"loss_gap": 2e-4, "grad_gap": 0.02, "change_gap": 0.02,
                            "moe_dropped": 0, "compiles_in_window": 0,
                            "last_loss_finite": 0}, **limits)}


@pytest.fixture(scope="module")
def ran():
    import jax

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "used": jax.devices()[:1]}
    args = types.SimpleNamespace(seed=2147485001, seconds=1.0, trace=0)
    with harness.interpret_kernels(True):
        out = train_arch.run(cell, args, device, harness.CompileMeter(), 0.0)
    return cell, out


def test_the_tiny_cell_is_correct_against_its_reference(ran):
    _, out = ran
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["checks"]["moe_dropped"]["value"] == 0.0
    assert set(out["end_to_end"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert out["run"]["resolutions"]["held_experts"]["rows"] == 2 * 96 * 4      # every pair
    assert "kda" not in out["run"]["resolutions"]


def test_the_router_is_live_and_the_window_counts_the_load(ran):
    cell, out = ran
    loads = out["run"]["ref"]["loads"]
    assert len(loads) == 2 and loads[0].shape == (4, 16)
    for load in loads:
        assert (load.sum(axis=1) == 2 * 96 * 4).all()          # k experts a token
        assert ((load > 0).sum(axis=1) > 8).all() and load.max() < 2 * 96
    moe = out["run"]["moe"]
    assert moe["steps"] == out["attempted"]
    assert 0 < moe["routed_slots"] / moe["steps"] <= 2 * 96 * 4 * 4
    # the counters alone carry the two readers that need no trace
    run = dict(out["run"], device={"kind": "TPU v5 lite"})
    got = harness.read_per_layer(["moe_routed_slots_per_step", "step_mfu.train_lfm2moe"], run)
    assert got["moe_routed_slots_per_step"] > 0 and got["step_mfu.train_lfm2moe"] > 0
    assert readers.pairs_per_token(run) == pytest.approx(
        moe["routed_slots"] / moe["steps"] / (2 * 96) / 4)


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
    bad = LR.train_steps(cell["model"], 2147485001, batches, 3e-3, param_dtype="bfloat16",
                         warmup_steps=cell["train"]["warmup_steps"], **kw)
    numbers = check.train_numbers(bad, ref)
    judged = check.judge(numbers, {k: cell["limits"][k] for k in numbers})
    print(fault, numbers)
    assert not all(c["ok"] for c in judged.values()), numbers


def test_required_work_of_the_published_configuration():
    """The issue's arithmetic: 469 M parameters held, 203 M multiply-adds a
    token (dense ffn 36%, the five operators 38%, flash's scores 8%, the held
    experts 9% and 10% with their routers, the head 8%), 1.22 GFLOP a token
    forward and backward."""
    cfg = harness.load_cell(CELL)["model"]
    params = sum(int(np.prod(s[1])) for s in LW.leaf_specs(cfg))
    assert round(params / 1e6, 1) == 469.3 and round(params * 14 / 1e9, 2) == 6.57
    weights = LRoof.matmul_params(cfg)
    scores = LRoof.roofline.attn_flops_fwd(cfg, 1, 4096.5) / 2
    total = weights + scores
    assert round(total / 1e6) == 203
    assert 1.21e9 < LRoof.train_flops_per_token(cfg, 8192) < 1.23e9
    d = LW.dims(cfg)
    shares = {"dense": 3 * d["h"] * d["dense"], "head": d["h"] * d["vocab"], "flash": scores,
              "experts": 4 * (d["h"] * 64 + 0.5 * LRoof.expert_weights(d)),
              "operators": 4 * 4 * d["h"] ** 2 + 2.5 * d["h"] ** 2}
    assert sum(shares.values()) == pytest.approx(total)
    assert {k: round(100 * v / total) for k, v in shares.items()} == {
        "dense": 36, "head": 8, "flash": 8, "experts": 10, "operators": 38}
    assert (LRoof.n_layers(cfg, "conv"), LRoof.n_layers(cfg, "full_attention"),
            LRoof.n_layers(cfg, "dense"), LRoof.n_layers(cfg, "moe")) == (4, 1, 1, 4)
    # the pairs the program counts move the experts' share and nothing else
    assert LRoof.matmul_params(cfg, 1.0) - weights == pytest.approx(
        4 * 0.5 * LRoof.expert_weights(d))
    flops, bytes_ = LRoof.expert_gmm(cfg, 12288)
    assert flops / bytes_ > 240          # compute-bound on a v5e
    flops, bytes_ = LRoof.flash_fwd(cfg, 3, 8192)
    assert flops == 3 * 4 * 32 * 64 * 8192 * 4096.5 and bytes_ == 3 * 8192 * 2 * (2048 + 512) * 2


def test_configuration_holds_every_published_key():
    """Every number of the catalog row's config under its own key, the three
    cuts with their published values, the deployment and what was assumed."""
    cfg = harness.load_json("configs", "lfm2-24b-a2b.json")
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 11776, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
                 "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
                 "num_key_value_heads": 8, "routed_scaling_factor": 1, "use_expert_bias": True,
                 "vocab_size": 65536,
                 "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert cfg["reduced"].keys() == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["published"] == value and cfg[key] == cfg["reduced"][key]["run"]
        else:
            assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == 40 and cfg["layer_types"].count("full_attention") == 10
    assert cfg["router_experts"] == 64 and "8 chips" in cfg["deployment"]
    assert {"tie_word_embeddings", "router", "conv", "weights", "learning_rate"} <= cfg["assumed"].keys()
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "lfm2-24b-a2b")
    assert set(entry["reduced"]) == cfg["reduced"].keys() and entry["source"] == cfg["source"]
    listed = [m["name"] for m in harness.cell_metrics(manifest, CELL, "per_layer")]
    assert set(OURS) <= set(listed) and "ce_stats_roofline" not in listed


# ---------------------------------------------------------------------------
# the readers, on a small recorded trace of this cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    path = os.path.join(HERE, "data", "lfm2_trace_v5e.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace of the cell yet")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    reduced = reduce.reduce_events(recorded, 1)
    reduced["events"] = recorded
    return dict(recorded["run"], cell=harness.load_cell(CELL), device={"kind": "TPU v5 lite"},
                trace=reduced)


@pytest.mark.parametrize("metric", OURS)
def test_trace_readers_find_their_kernels(traced_run, metric):
    value = harness.read_per_layer([metric], traced_run)[metric]
    assert 0.0 < value <= 100.0


def test_roofline_readers_count_the_kernels_own_events(traced_run):
    """One `flash_dq` an attention layer and step, three `grouped_matmul_dw`
    an expert layer and step; doubling every kernel event and its time leaves
    a share where it was (no count of programs in the slice enters)."""
    trace = traced_run["trace"]
    steps = readers.events(trace, "flash_dq")
    assert steps > 0 and readers.events(trace, "grouped_matmul_dw") == pytest.approx(12 * steps)
    names = ["lfm2_flash_roofline", "lfm2_expert_gmm_roofline"]
    before = harness.read_per_layer(names, traced_run)
    twice = dict(traced_run, trace=dict(
        trace, op_s={k: 2 * v for k, v in trace["op_s"].items()},
        op_n={k: 2 * v for k, v in trace["op_n"].items()}))
    assert harness.read_per_layer(names, twice) == pytest.approx(before)
    assert named.kernel_seconds(trace, "ce_stats") > 0


def test_readers_return_nothing_without_their_kernels():
    """A program without the kernels or the counters, or of another
    architecture: every reader returns None and the line leaves the metric
    out, and none raises."""
    empty = reduce.reduce_events({"devices": {"/device:TPU:0": {"ops": [
        ["%fusion.1 = bf16[8] fusion()", 0.0, 10.0]], "modules": []}}, "host": []}, 1)
    run = {"cell": harness.load_cell(CELL), "device": {"kind": "TPU v5 lite"},
           "trace": empty, "trace_window_s": 1.0}
    assert harness.read_per_layer(OURS, run) == {}
    assert harness.read_per_layer(OURS, dict(run, trace=None)) == {}
    other = dict(run, cell=harness.load_cell("train_kimilinear_seq8k"),
                 tokens_per_s_per_chip=1.0, tokens_per_step=16384,
                 moe={"steps": 2, "routed_slots": 8.0})
    assert harness.read_per_layer(OURS, other) == {}
