"""`benchmark/scopes.py` and the `scope_ms_per_step.*` readers on small
made-up traces and on one recorded Moonlight step with the table of the same
program: the parts partition the step program's busy union, a loop keeps
what its body leaves, and every reader returns None where there is nothing
to read (no table, another module's table, no whole step, a program without
`paddle_tpu.observability.scopes`)."""
import gzip
import json
import os
import sys

import pytest

from benchmark import harness, reduce, scopes


@pytest.fixture
def table_is(monkeypatch):
    def use(table):
        monkeypatch.setattr(scopes, "_program_table", lambda: table)
    return use


def test_a_program_without_the_table_reads_none(monkeypatch):
    import paddle_tpu.observability as observability

    monkeypatch.delattr(observability, "scopes", raising=False)
    monkeypatch.setitem(sys.modules, "paddle_tpu.observability.scopes", None)
    assert scopes._program_table() is None


def _two_steps():
    """Two steps of a program whose `while` (in `head`) holds two operations
    (`attn`, `mlp`), small programs before and after: (trace, table)."""
    table = {"module": "jit__step_fn",
             "ops": {"while.1": "head", "fusion.2": "attn", "fusion.3": "mlp",
                     "fusion.4": "optimizer"}}
    ops, modules = [], []
    for t in (1000.0, 2000.0):
        modules.append(["jit__step_fn(7)", t, 900.0])
        ops += [["%while.1 = (f32[]) while(...)", t, 500.0],
                ["%fusion.2 = f32[] fusion(...)", t + 100, 100.0],
                ["%fusion.3 = f32[] fusion(...)", t + 250, 150.0],
                ["%fusion.4 = f32[] fusion(...)", t + 600, 250.0],
                ["%fusion.9 = f32[] fusion(...)", t + 860, 20.0]]
    events = {"host": [], "lines": {}, "devices": {"/device:TPU:0": {
        "ops": ops + [["%small.1 = f32[] add(...)", 0.0, 10.0],
                      ["%small.2 = f32[] add(...)", 3990.0, 10.0]],
        "modules": modules + [["jit_small(1)", 0.0, 10.0], ["jit_small(1)", 3990.0, 10.0]]}}}
    trace = reduce.reduce_events(events)
    trace["events"] = events
    return trace, table


def test_a_loop_keeps_what_its_body_leaves(table_is):
    """Every nanosecond of the step's busy union is counted once: a `while`
    keeps what its body's operations leave."""
    trace, table = _two_steps()
    pieces = scopes.step_pieces(trace, table["module"], "/device:TPU:0")
    union = reduce._length(reduce._union([(a, b) for _, a, b in pieces]))
    assert sum(scopes.innermost_seconds(pieces).values()) == union == 1540.0
    table_is(table)
    ms = scopes.ms_per_step({"trace": trace})
    assert reduce.steps_measured(trace) == pytest.approx(2.0)
    assert ms == pytest.approx({"head": 250e-6, "attn": 100e-6, "mlp": 150e-6,
                                "optimizer": 250e-6, "unscoped": 20e-6})


def test_nothing_to_read_is_none(table_is):
    trace, table = _two_steps()
    table_is(None)
    assert scopes.ms_per_step({"trace": trace}) is None
    table_is({"module": "jit_engine_decode", "ops": table["ops"]})
    assert scopes.ms_per_step({"trace": trace}) is None
    table_is(table)
    assert scopes.ms_per_step({}) is None
    # a slice that cuts both steps holds no whole one
    cut = dict(trace["events"], host=[[reduce.SLICE_SPAN, 1500.0, 1000.0]])
    half = reduce.reduce_events(cut)
    half["events"] = cut
    assert scopes.ms_per_step({"trace": half}) is None


def test_every_scope_metric_reads_its_part(table_is):
    trace, table = _two_steps()
    table_is(table)
    run = {"trace": trace}
    parts = scopes.ms_per_step(run)
    manifest = harness.load_manifest()
    names = [m["name"] for m in manifest["per_layer"] if m["name"].startswith("scope_ms_per_step.")]
    assert len(names) == 12
    values = harness.read_per_layer(names, run)
    for name in names:
        assert values[name] == parts.get(name.split(".", 1)[1], 0.0), name


# ---------------------------------------------------------------------------
# one whole step of the Moonlight cell recorded on a TPU v5e, with its table
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "data", "moonlight_scopes_v5e.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    table = rec.pop("table")
    trace = reduce.reduce_events(rec, 1)
    trace["events"] = rec
    return trace, table


def test_the_recorded_step_partitions_its_busy_union(recorded, table_is):
    """Self times sum to the step program's busy union to the nanosecond; the
    slice holds one whole step; every part the cell has reads time, and the
    operations the table cannot place stay under 3% of the step."""
    trace, table = recorded
    pieces = scopes.step_pieces(trace, table["module"], "/device:TPU:0")
    union = reduce._length(reduce._union([(a, b) for _, a, b in pieces]))
    assert sum(scopes.innermost_seconds(pieces).values()) == pytest.approx(union, abs=1e3)
    table_is(table)
    parts = scopes.ms_per_step({"trace": trace})
    assert reduce.steps_measured(trace) == pytest.approx(1.0)
    assert sum(parts.values()) == pytest.approx(union / 1e6, abs=1e-3)
    for part in ("embed", "attn", "mlp", "moe_router", "moe_layout", "moe_experts",
                 "moe_shared", "head", "optimizer"):
        assert parts.get(part, 0.0) > 0, part
    assert parts.get("kda", 0.0) == parts.get("conv_mixer", 0.0) == 0.0
    assert parts.get("unscoped", 0.0) <= 0.03 * sum(parts.values())


def test_the_recorded_step_without_its_table_reads_none(recorded, table_is):
    trace, table = recorded
    table_is(None)
    assert scopes.ms_per_step({"trace": trace}) is None
    table_is({"module": "jit__other_fn", "ops": table["ops"]})
    assert scopes.ms_per_step({"trace": trace}) is None
    # half the step: no whole run of the program in the slice
    table_is(table)
    events = trace["events"]
    (run,) = [m for m in events["devices"]["/device:TPU:0"]["modules"]
              if m[0].startswith(table["module"] + "(")]
    cut = dict(events, host=[[reduce.SLICE_SPAN, run[1], run[2] / 2]])
    half = reduce.reduce_events(cut, 1)
    half["events"] = cut
    assert scopes.ms_per_step({"trace": half}) is None
