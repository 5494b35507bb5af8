"""The kind `train_arch` and the Kimi-Linear files it takes (weights,
reference, roofline, readers), on the CPU at toy sizes with the kernels
interpreted: `--rehearsal` reads rehearsal.json by kind and cannot rehearse a
new kind, so the tiny cell is built here. Nothing here is a measurement."""
import dataclasses
import gzip
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, named, reduce
from benchmark.arch.kimi_linear import readers
from benchmark.arch.kimi_linear import reference as KR
from benchmark.arch.kimi_linear import roofline as KRoof
from benchmark.arch.kimi_linear import weights as KW
from benchmark.kinds import train_arch

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny_cell(**limits) -> dict:
    from paddle_tpu.models import kimi_linear_tiny_config

    model = dataclasses.asdict(kimi_linear_tiny_config(router_bias_update_rate=0.01))
    model.update(dtype="bfloat16", arch="kimi_linear")
    return {"name": "tiny", "kind": "train_arch", "chips": 1, "mesh": None, "model": model,
            "mix": {"family": "token_stream", "rows": 2, "seq_len": 96},
            "train": {"learning_rate": 3e-3, "warmup_steps": 4, "weight_decay": 0.01, "batches": 8,
                      "check_steps": 2, "log_every": 2},
            "trace_s": 0.2,
            "limits": dict({"loss_gap": 1e-3, "grad_gap": 0.04, "change_gap": 0.02,
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


def test_the_run_carries_what_the_program_resolved(ran):
    """The readers divide by the program's own block of heads and rows laid
    out for the held experts (`last_resolution`), never by a copy of a rule."""
    _, out = ran
    res = out["run"]["resolutions"]
    assert res["kda"]["head_block"] in (1, 2, 4) and res["kda"]["chunk"] == 64
    assert res["held_experts"]["rows"] == 2 * 96 * 4 * 4 // 16 * 4     # 4 shares of 4 in 16
    assert readers.kda_calls_per_layer(dict(out["run"], cell=out["run"]["cell"])) > 0
    assert readers.kda_calls_per_layer({"cell": out["run"]["cell"]}) is None


def test_the_router_is_live_in_the_checked_steps(ran):
    """Seeded, non-zero router weights: the held experts see a share of the
    pairs, neither none nor all, and the loads differ from expert to expert."""
    _, out = ran
    loads = out["run"]["ref"]["loads"]
    assert len(loads) == 2 and loads[0].shape == (4, 16)
    for load in loads:
        assert (load.sum(axis=1) == 2 * 96 * 4).all()          # k experts a token
        assert ((load > 0).sum(axis=1) > 8).all() and load.max() < 2 * 96
    assert np.abs(out["run"]["ref"]["biases"]).max() == pytest.approx(0.02)


def test_the_window_counts_the_expert_load(ran):
    cell, out = ran
    moe = out["run"]["moe"]
    assert moe["steps"] == out["attempted"]
    per_step = moe["routed_slots"] / moe["steps"]
    pairs = cell["mix"]["rows"] * cell["mix"]["seq_len"] * 4 * 4      # k x expert layers
    assert 0 < per_step <= pairs


@pytest.mark.parametrize("fault", ["half_batch", "other_experts", "float8"])
def test_the_faults_and_the_control_fail(ran, fault):
    """Half of the batch left out, experts 5-8 computed in place of 1-4, and
    the reference with float8 projections: each must read over a limit."""
    from benchmark import check
    from benchmark import reference as R

    cell, out = ran
    ref, batches = out["run"]["ref"], [(b[:, :-1], b[:, 1:]) for b in out["run"]["check_batches"]]
    kw = {"half_batch": {"rows": slice(0, 1)}, "other_experts": {"first": 4},
          "float8": {"mm": R.mm_fp8}}[fault]
    bad = KR.train_steps(cell["model"], 2147485001, batches, 3e-3, param_dtype="bfloat16",
                         warmup_steps=cell["train"]["warmup_steps"], **kw)
    numbers = check.train_numbers(bad, ref)
    judged = check.judge(numbers, {k: cell["limits"][k] for k in numbers})
    print(fault, numbers)
    assert not all(c["ok"] for c in judged.values()), numbers


def test_required_work_of_the_published_configuration():
    """The issue's arithmetic: 602 M parameters held, 335.6 M weights a
    token, 2.3 GFLOP a token forward and backward at 8192."""
    cfg = harness.load_cell("train_kimilinear_seq8k")["model"]
    params = sum(int(np.prod(s[1])) for s in KW.leaf_specs(cfg))
    assert round(params / 1e6, 1) == 602.4
    assert round(KRoof.matmul_params(cfg) / 1e6, 1) == 335.6
    assert 2.2e9 < KRoof.train_flops_per_token(cfg, 8192) < 2.5e9
    assert KRoof.n_layers(cfg, "kda") == 4 and KRoof.n_layers(cfg, "mla") == 1
    assert KRoof.n_layers(cfg, "moe") == 4
    # a tenth of the work of a token is the one MLA layer's attention at 8k
    attn = 3 * KRoof.mla_attn_flops_fwd(cfg, 1, 4096.5)
    assert 0.09 < attn / KRoof.train_flops_per_token(cfg, 8192) < 0.13
    flops, bytes_ = KRoof.kda_scan_fwd(cfg, 16384)
    assert flops / bytes_ < 240          # memory-bound on a v5e
    flops, bytes_ = KRoof.expert_gmm(cfg, 4096)
    assert flops / bytes_ > 240          # compute-bound


def test_configuration_holds_every_published_key():
    cfg = harness.load_json("configs", "kimi-linear-48b-a3b.json")
    assert cfg["reduced"].keys() == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"]
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_token"]) == (2304, 1024, 9216, 512, 128,
                                                                  64, 128, 8)
    assert cfg["router_experts"] == 256 and "32 chips" in cfg["deployment"]


# ---------------------------------------------------------------------------
# the readers, on a small recorded trace of this cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    path = os.path.join(HERE, "data", "kimi_trace_v5e.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace of the cell yet")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    reduced = reduce.reduce_events(events, 1)
    reduced["events"] = events
    cell = harness.load_cell("train_kimilinear_seq8k")
    return {"cell": cell, "device": {"kind": "TPU v5 lite"}, "trace": reduced,
            "trace_window_s": 0.7, "tokens_per_s_per_chip": 15273.5, "tokens_per_step": 16384,
            "resolutions": {"kda": {"chunk": 64, "head_block": 4},
                            "held_experts": {"rows": 16384, "block_rows": 128}},
            # the counters of the run the slice was cut from (seed 3100000019, PR 27)
            "moe": {"steps": 43, "routed_slots": 751724.0, "dropped": 0.0,
                    "max_expert_load": 60037.0, "mean_expert_load": 23491.375}}


@pytest.mark.parametrize("metric", ["kda_fwd_roofline", "kda_bwd_roofline", "mla_flash_roofline",
                                    "expert_gmm_roofline", "new_kernels_step_share_pct"])
def test_trace_readers_find_their_kernels(traced_run, metric):
    value = harness.read_per_layer([metric], traced_run)[metric]
    assert 0.0 < value <= 100.0


def test_roofline_readers_count_the_kernels_own_events(traced_run):
    trace = traced_run["trace"]
    assert readers.events(trace, "kda_bwd") > 0
    # doubling every kernel event and its time leaves a share where it was
    cell = traced_run["cell"]
    before = harness.read_per_layer(["kda_bwd_roofline"], traced_run)["kda_bwd_roofline"]
    twice = dict(traced_run, trace=dict(
        trace, op_s={k: 2 * v for k, v in trace["op_s"].items()},
        op_n={k: 2 * v for k, v in trace["op_n"].items()}))
    after = harness.read_per_layer(["kda_bwd_roofline"], twice)["kda_bwd_roofline"]
    assert after == pytest.approx(before)
    assert named.kernel_seconds(trace, "kda_fwd") > 0 and cell["chips"] == 1


def test_counter_readers(traced_run):
    got = harness.read_per_layer(["moe_routed_slots_per_step", "moe_expert_load_max_over_mean",
                                  "step_mfu.train_kimilinear"], traced_run)
    assert got["moe_routed_slots_per_step"] == pytest.approx(17481.95, rel=1e-5)   # share 16,384
    assert got["moe_expert_load_max_over_mean"] == pytest.approx(2.5557, rel=1e-4)
    assert 17 < got["step_mfu.train_kimilinear"] < 19          # 18.05 on the chip (PR 27)


def test_readers_return_nothing_without_their_kernels():
    """A program without the kernels or the counters (the parent): every
    reader returns None and the line leaves the metric out."""
    empty = reduce.reduce_events({"devices": {"/device:TPU:0": {"ops": [
        ["%fusion.1 = bf16[8] fusion()", 0.0, 10.0]], "modules": []}}, "host": []}, 1)
    run = {"cell": harness.load_cell("train_kimilinear_seq8k"), "device": {"kind": "TPU v5 lite"},
           "trace": empty, "trace_window_s": 1.0, "tokens_per_s_per_chip": 1.0}
    names = ["kda_fwd_roofline", "kda_bwd_roofline", "mla_flash_roofline", "expert_gmm_roofline",
             "new_kernels_step_share_pct", "moe_routed_slots_per_step",
             "moe_expert_load_max_over_mean"]
    assert harness.read_per_layer(names, run) == {}
