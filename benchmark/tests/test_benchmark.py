"""CPU tests of the benchmark's own files: `pytest benchmark/tests`.

They hold the loader to the contract (a cell, a configuration, a mix or a
per-layer metric is files plus entries), the yardstick to hand-worked counts
and a recorded trace, the reference to the program's model at a tiny size,
and `correct` to coming out false for the control and for every planted
fault. Nothing here is a measurement.
"""
from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import check, harness, reduce, reference, roofline, traffic, weights

MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# every cell file, in the manifest or kept for a later PR (PERF.md, Open questions)
CELL_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(harness.HERE, "workloads")))
REHEARSED = set(harness.load_json("rehearsal.json")["cell"])   # kinds with tiny sizes
KIND_CELLS: dict = {}                     # the first one-chip cell of each kind
for _c in CELLS + CELL_FILES:
    if harness.load_cell(_c)["chips"] == 1:
        KIND_CELLS.setdefault(harness.load_cell(_c)["kind"], _c)


# ---- the loader and the contract ---------------------------------------------

@pytest.mark.parametrize("name", CELL_FILES)
def test_every_cell_file_loads(name):
    cell = harness.load_cell(name)
    assert cell["kind"] in ("train", "train_arch", "serve") and cell["chips"] in (1, 4)
    assert os.path.exists(os.path.join(harness.HERE, "kinds", f"{cell['kind']}.py"))
    assert cell["mix"]["family"] == {"train": "token_stream", "train_arch": "token_stream",
                                     "serve": "requests"}[cell["kind"]]
    assert cell["model"]["hidden_size"] and "compiles_in_window" in cell["limits"]
    if cell["kind"] in REHEARSED:         # an architecture's kind has its own CPU tests
        assert harness.load_cell(name, rehearsal=True)["model"]["hidden_size"] == 64
    else:
        assert os.path.isdir(os.path.join(harness.HERE, "arch", cell["model"]["arch"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    cell = harness.load_cell(name)
    assert cell["config"] == entry["config"] and cell["traffic"] == entry["traffic"]
    assert cell["chips"] == entry["chips"]
    config = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert config["file"] == f"benchmark/configs/{cell['config']}.json"
    assert sorted(cell["model"]["reduced"]) == sorted(config["reduced"])
    assert cell["model"]["source"] == config["source"]
    e2e = [m["name"] for m in harness.cell_metrics(MANIFEST, name, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.cell_metrics(MANIFEST, name, "per_layer")
    assert any("mfu" in m["name"] for m in layer)
    assert any(m["name"].startswith("device_idle_pct") for m in layer)
    for m in layer:
        assert m["moves"] in e2e, f"{m['name']} moves a metric {name} does not report"
    # a cell in the manifest has limits set from readings, not placeholders
    assert all(v < 1 for k, v in cell["limits"].items() if k.endswith("_gap"))


def test_manifest_keeps_to_the_contracts_form():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and name.match(c["name"])
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200, (w["name"], len(w["why"]))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in sources and name.match(m["name"])
        assert m["moves"] in [e["name"] for e in MANIFEST["end_to_end"]]
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(names)) == len(names)
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", sorted(
    f[:-3] for f in os.listdir(os.path.join(harness.HERE, "metrics")) if f.endswith(".py")))
def test_every_per_layer_metric_has_a_reader(metric):
    path = os.path.join(harness.HERE, "metrics", f"{metric}.py")
    assert os.path.exists(path)
    assert "def read(run)" in open(path).read()


def test_widths_are_the_published_ones():
    m = harness.load_json("configs", "mistral-7b-v0.3.json")
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["vocab_size"]) == (4096, 14336, 32, 8, 32768)
    assert list(m["reduced"]) == ["num_hidden_layers"]
    i = harness.load_json("configs", "internlm2-1.8b.json")
    assert (i["hidden_size"], i["intermediate_size"], i["num_attention_heads"],
            i["num_key_value_heads"], i["vocab_size"], i["num_hidden_layers"]) == (
        2048, 8192, 16, 8, 92544, 24)
    assert i["reduced"] == {}


def test_a_reader_that_finds_nothing_returns_nothing():
    run = {"cell": harness.load_cell(KIND_CELLS["train"]), "device": {"kind": "TPU v5 lite"},
           "trace": None, "spans": [], "queue_waits": [], "records": [], "tokens_per_step": 12288}
    out = harness.read_per_layer(["flash_roofline.train", "ce_stats_roofline",
                                  "device_idle_pct.train", "engine_decode_step_ms"], run)
    assert out == {}


def test_unknown_device_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9")
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


# ---- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat", "doc"])
def test_requests_repeat_for_a_seed_and_differ_between_seeds(mix):
    spec = harness.load_json("traffic", f"{mix}.json")
    a = traffic.requests(spec, 2**31 + 11, 20.0, 1000)
    b = traffic.requests(spec, 2**31 + 11, 20.0, 1000)
    c = traffic.requests(spec, 12, 20.0, 1000)
    assert len(a) == round(spec["rate_per_s"] * 20)
    assert all(x["due_s"] == y["due_s"] and np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"] for x, y in zip(a, b))
    assert any(not np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    # every seed gets the same set of sizes, in another order
    assert sorted(len(x["prompt"]) for x in a) == sorted(len(x["prompt"]) for x in c)
    assert sorted(x["max_new_tokens"] for x in a) == sorted(x["max_new_tokens"] for x in c)
    lo, hi = traffic.warmup_lengths(spec)
    assert all(lo <= len(x["prompt"]) <= hi for x in a)
    assert all(0 <= x["due_s"] < 20.0 for x in a)
    assert [x["due_s"] for x in a] == sorted(x["due_s"] for x in a)


def test_token_batches_repeat_and_rows_differ():
    spec = harness.load_json("traffic", "seq4k.json")
    small = dict(spec, seq_len=64)
    a = traffic.token_batches(small, 7, 4, 32768)
    assert a.shape == (4, spec["rows"], 65) and a.dtype == np.int32
    assert np.array_equal(a, traffic.token_batches(small, 7, 4, 32768))
    assert not np.array_equal(a, traffic.token_batches(small, 8, 4, 32768))
    assert not np.array_equal(a[0, 0], a[0, 1])


# ---- roofline: hand-worked counts ---------------------------------------------

MISTRAL = harness.load_json("configs", "mistral-7b-v0.3.json")
INTERN = harness.load_json("configs", "internlm2-1.8b.json")


def test_roofline_counts():
    # one Mistral layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three of 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert roofline.layer_matmul_params(MISTRAL) == layer == 218_103_808
    assert roofline.matmul_params(MISTRAL) == 2 * layer + 4096 * 32768
    # a token in a causal row of 4096 meets 2048.5 keys a layer: 4 x 32 x 128 x 2048.5
    attn = 4 * 32 * 128 * 2048.5
    assert roofline.train_flops_per_token(MISTRAL, 4096) == pytest.approx(
        3 * (2 * (2 * layer + 4096 * 32768) + 2 * attn))
    # flash forward over 3 rows of 4096: compute-bound on a v5e
    flops, byts = roofline.flash_fwd(MISTRAL, 3, 4096)
    assert flops == pytest.approx(3 * 4096 * attn)
    assert byts == 3 * 4096 * (2 * 4096 + 2 * 1024) * 2
    assert roofline.least_seconds(flops, byts, roofline.peaks("TPU v5 lite"))[1] == "compute"
    assert roofline.flash_bwd(MISTRAL, 3, 4096)[0] == pytest.approx(2.5 * flops)
    # the head over 12288 tokens
    assert roofline.fused_ce(MISTRAL, 12288)[0] == 2 * 12288 * 4096 * 32768
    # InternLM2: 96 KiB of K and V a token over 24 layers; decode is memory-bound
    flops, byts = roofline.paged_decode(INTERN, 10_000, 32)
    assert 24 * 2 * 8 * 128 * 2 == 96 * 1024
    assert byts == 10_000 * 2 * 1024 * 2 + 32 * 2 * 2048 * 2
    assert roofline.least_seconds(flops, byts, roofline.peaks("TPU v5 lite"))[1] == "memory"
    assert roofline.n_params(INTERN) == pytest.approx(1.889e9, rel=1e-3)
    assert roofline.decode_flops(INTERN, 0) == 2 * roofline.matmul_params(INTERN)


# ---- reduce ---------------------------------------------------------------------

def test_reduce_arithmetic_on_hand_made_events():
    ev = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0.0, 4e9], ["all-reduce.1", 3e9, 3e9], ["fusion.2", 8e9, 1e9],
                ['%fn.2 = bf16[8] custom-call(bf16[8,4096,16,128] %p), custom_call_target="tpu_custom_call"',
                 9e9, 1e9]],
        "modules": [["jit_step", 0.0, 6e9], ["jit_step", 8e9, 2e9]]}},
        "host": [["train.step", 0.0, 10e9], ["train.read_loss", 6.5e9, 1e9]]}
    r = reduce.reduce_events(ev, 1)
    assert r["busy_s"] == pytest.approx(8.0)            # [0,6] + [8,10]
    assert r["collective_s"] == pytest.approx(3.0)
    assert r["exposed_collective_s"] == pytest.approx(2.0)   # [4,6]
    assert reduce.pallas_seconds(r, has=reduce.dims(8, 4096, 16, 128)) == pytest.approx(1.0)
    assert reduce.pallas_seconds(r, lacks=reduce.dims(8, 4096, 16, 128)) == 0
    # no `bench.slice` span: the slice is the events' extent, [0, 10], both
    # runs of the program touch an end, and 8 s of it over the longer is no count
    assert r["window_s"] == pytest.approx(10.0)
    assert reduce.main_module_runs(r) == pytest.approx(8 / 6)
    assert reduce.steps_measured(r) is None
    assert r["idle_gaps"][0] == ["train.read_loss", pytest.approx(2.0)]
    assert r["device_ops"][0][0] == "fusion.1"


def test_reduce_on_the_recorded_trace():
    path = os.path.join(os.path.dirname(__file__), "data", "train_trace_v5e.json.gz")
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    assert any(p.startswith(reduce.DEVICE_PLANE) for p in ev["devices"])
    assert all(reduce.OPS_LINE in ev["lines"][p] for p in ev["devices"])
    r = reduce.reduce_events(ev, 1)
    ops = ev["devices"][r["planes"][0]]["ops"]
    window = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0.9 * window < r["busy_s"] <= window     # a training step keeps the chip busy
    assert reduce.main_module_runs(r) >= 1
    head = reduce.dims(MISTRAL["hidden_size"], MISTRAL["vocab_size"])
    assert reduce.pallas_seconds(r, has=head) > 0          # the fused CE kernel
    assert reduce.pallas_seconds(r, lacks=head) > 0        # flash forward and backward
    assert any(n == "train.step" for n, _, _ in ev["host"])


# ---- the reference against the program's model, float32, tiny ---------------------

TINY = dict(harness.load_json("rehearsal.json")["model"], rope_theta=1e6, rms_norm_eps=1e-5,
            dtype="float32")


def test_reference_agrees_with_the_programs_model():
    import jax.numpy as jnp

    import paddle_tpu as paddle

    model = harness.seeded_model(TINY, 5)
    model.eval()
    ids = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 48)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    loss = float(model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels)))
    leaves = [x.astype(jnp.float32)
              for x in weights.make_all(5, weights.leaf_specs(TINY), "float32")]
    want = float(reference.batch_loss(leaves, jnp.asarray(ids), jnp.asarray(labels), TINY,
                                      reference.mm_f32)) / ids.size
    assert loss == pytest.approx(want, rel=2e-5)
    logits = np.asarray(model(paddle.to_tensor(ids[:1]))._value)[0]
    gaps = reference.served_logit_gaps(TINY, 5, [np.concatenate([ids[0], [0]])], [1],
                                       param_dtype="float32")
    # greedy tokens of the program's own logits lie on the reference's best
    served = np.concatenate([ids[0][:1], logits.argmax(-1)])
    gaps = reference.served_logit_gaps(TINY, 5, [served[:2]], [1], param_dtype="float32")
    assert gaps["served"][0].max() < 1e-5


# ---- `correct`: a rehearsal run of each kind, the control, the faults -------------

def _args(seed, seconds=1.0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)


def _run_kind(kind, seed=3, seconds=1.0):
    import importlib
    import time

    cell = harness.load_cell(KIND_CELLS[kind], rehearsal=True)
    device = harness.open_device(cell["chips"], rehearsal=True)
    mod = importlib.import_module(f"benchmark.kinds.{kind}")
    with harness.interpret_kernels(True):
        return cell, mod.run(cell, _args(seed, seconds), device, _METER, time.perf_counter())


class _Meter:
    traces = 0
    secs = 0.0


_METER = _Meter()


@pytest.mark.parametrize("kind", sorted({harness.load_cell(c)["kind"] for c in CELLS} & REHEARSED))
def test_rehearsal_last_line_has_the_contracts_keys(kind):
    """run.py end to end, for each kind that has a cell in the manifest and
    tiny sizes in `rehearsal.json` (a kind without a cell is driven in-process
    by the control tests below; `train_arch` by `test_train_arch.py`)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for trace in ("0", "1"):
        p = subprocess.run([sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
                            KIND_CELLS[kind], "--seed", str(2**31 + 5), "--seconds", "2",
                            "--trace", trace, "--rehearsal"], capture_output=True, text=True,
                           env=env, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert list(line)[-1] == "checks" and line["rehearsal"] is True
        assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
        assert line["correct"] is True and line["device"]["platform"] == "cpu"
        assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
        if trace == "0":
            assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        else:
            assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
            # the CPU has no device plane: nothing busy, and the slice's span is there
            assert 0 == line["device"]["busy_s"] < line["device"]["window_s"] < 2
            assert "compile_s" in line["metrics"]
        assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_no_chip_no_result():
    p = subprocess.run([sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_train_control_and_faults_come_out_not_correct(monkeypatch):
    from benchmark.kinds import train

    cell, out = _run_kind("train")
    assert out["correct"], out["checks"]
    ref, batches = out["run"]["ref"], [(b[:, :-1], b[:, 1:]) for b in out["run"]["check_batches"]]
    lr, limits = cell["train"]["learning_rate"], cell["limits"]
    prog = {k: c["value"] for k, c in out["checks"].items()}

    # the control: the reference in float8 in the program's place
    ctl = check.train_numbers(reference.train_steps(
        cell["model"], 3, batches, lr, mm=reference.mm_fp8, param_dtype="bfloat16"), ref)
    assert not all(c["ok"] for c in check.judge(dict(prog, **ctl), limits).values())
    assert max(ctl[k] / prog[k] for k in ("loss_gap", "grad_gap")) >= 3

    # fault: half of the batch left out, the mean taken over the rest
    real_feed = train._feed
    monkeypatch.setattr(train, "_feed", lambda b: real_feed(b[: max(len(b) // 2, 1)]))
    _, half = _run_kind("train")
    assert not half["correct"] and not half["checks"]["grad_gap"]["ok"]
    monkeypatch.setattr(train, "_feed", real_feed)

    # fault: a step that returns its state unchanged
    real_build = train.build

    def frozen_build(*a):
        model, opt, step = real_build(*a)
        call = type(step).__call__

        def unchanged(self, *batch):
            import jax
            import jax.numpy as jnp

            # copies: the step donates what it is given
            params, states = jax.tree_util.tree_map(
                jnp.copy, (self._param_vals, self._opt_states))
            loss = call(self, *batch)
            self._param_vals, self._opt_states = params, states
            return loss

        step.__class__ = type("Frozen", (type(step),), {"__call__": unchanged})
        return model, opt, step

    monkeypatch.setattr(train, "build", frozen_build)
    _, frozen = _run_kind("train")
    assert not frozen["correct"]
    assert frozen["checks"]["change_gap"]["value"] == pytest.approx(1.0)
    assert frozen["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_serve_control_and_fault_come_out_not_correct(monkeypatch):
    from benchmark.kinds import serve

    cell, out = _run_kind("serve", seconds=3.0)
    assert out["correct"], out["checks"]
    assert out["run"]["checked_tokens"] > 0
    gaps = reference.served_logit_gaps(cell["model"], 3, out["run"]["sequences"],
                                       out["run"]["n_prompt"], mm_names=("f32", "fp8"),
                                       pad_to=cell["engine"]["max_seq_len"])
    control = max(float(g.max()) for g in gaps["fp8"])
    assert control > cell["limits"]["logit_gap"] >= out["checks"]["logit_gap"]["value"]

    # fault: a token altered where it is produced
    real = serve.Window._on_token
    monkeypatch.setattr(serve.Window, "_on_token",
                        lambda self, req, tok: real(self, req, (tok + 1) % cell["model"]["vocab_size"]))
    _, bad = _run_kind("serve", seconds=3.0)
    assert not bad["correct"] and not bad["checks"]["logit_gap"]["ok"]
