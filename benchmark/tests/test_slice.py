"""The traced slice on the trace's own clock (`reduce.slice_interval`,
`reduce_events`' clipping, `reduce.main_module_runs`), on the recorded traces
and on hand-made ones, and `harness.Profile`'s `bench.slice` span through the
CPU's profiler. Nothing here is a measurement."""
from __future__ import annotations

import gzip
import json
import os
import time

import pytest

from benchmark import harness, named, reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = sorted(f[:-8] for f in os.listdir(DATA) if f.endswith(".json.gz"))
KERNEL = ('%ce_stats.1 = f32[8] custom-call(bf16[8,4096] %p), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={"kernel":"ce_stats"}}')
MS = 1e6


def _recorded(name):
    with gzip.open(os.path.join(DATA, f"{name}.json.gz"), "rt") as f:
        return json.load(f)


def _steps(first, n, step=100 * MS, kernel=(30 * MS, 10 * MS), span=None, gap=0.0):
    """`n` back-to-back runs of `jit_step` from `first` on, each one fusion
    long (less `gap` at its end) with the kernel inside it."""
    ops, modules = [], []
    for i in range(n):
        t = first + i * step
        modules.append(["jit_step(1)", t, step - gap])
        ops.append(["%fusion.1 = bf16[8] fusion()", t, step - gap])
        ops.append([KERNEL, t + kernel[0], kernel[1]])
    host = [[reduce.SLICE_SPAN, span[0], span[1] - span[0]]] if span else []
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def _run(ev):
    r = reduce.reduce_events(ev, 1)
    r["events"] = ev
    return {"trace": r, "trace_window_s": r["window_s"]}


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_traces_busy_lies_inside_the_window(name):
    ev = _recorded(name)
    r = reduce.reduce_events(ev, 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = harness.read_per_layer(["device_idle_pct.train"],
                                  {"trace": r, "trace_window_s": r["window_s"]})
    assert 0 <= idle["device_idle_pct.train"] < 10
    assert sum(g[1] for g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    has_span = any(n == reduce.SLICE_SPAN for n, _, _ in ev["host"])
    assert has_span == name.startswith("slice_")
    if has_span:     # a whole slice: the span bounds it, and it holds whole steps
        (s, d), = [(s, d) for n, s, d in ev["host"] if n == reduce.SLICE_SPAN]
        lo, hi = r["interval_ns"]
        assert s <= lo < s + 0.01e9 and hi == s + d
        assert reduce.steps_measured(r) > 1


def test_events_that_overhang_the_slice_are_clipped_to_it():
    """The profiler stops some milliseconds after the slice was closed: device
    events that run 5 ms past `hi` with no idle gap read busy == window."""
    ev = _steps(-50 * MS, 11, span=(0.0, 1000 * MS))            # events end at 1050 ms
    assert max(s + d for _, s, d in ev["devices"]["/device:TPU:0"]["ops"]) == 1050 * MS
    r = reduce.reduce_events(ev, 1)
    assert r["window_s"] == pytest.approx(1.0) and r["busy_s"] == r["window_s"]
    assert r["idle_gaps"] == []
    idle = harness.read_per_layer(["device_idle_pct.train"], _run(ev))
    assert idle["device_idle_pct.train"] == 0.0
    # time by operation is clipped too: 10 steps' worth of each, not 11
    assert r["op_s"][KERNEL] == pytest.approx(0.1)
    assert r["op_n"][KERNEL] == pytest.approx(10.0)


def test_the_slice_begins_with_the_devices_record_and_ends_with_the_span():
    """The device's record begins some milliseconds after the span opens:
    what came before it is unknown, not idle, and the run it cut is cut. A
    device that falls silent before the span closes is idle, a read of the
    loss here."""
    ev = _steps(200 * MS, 3, span=(198 * MS, 1000 * MS), gap=1 * MS)
    ev["host"].append(["train.read_loss", 490 * MS, 600 * MS])
    r = reduce.reduce_events(ev, 1)
    assert r["interval_ns"] == [200 * MS, 1000 * MS]
    assert r["busy_s"] == pytest.approx(0.297) and r["window_s"] == pytest.approx(0.8)
    assert r["idle_gaps"][0] == ["train.read_loss", pytest.approx(0.501)]
    assert [g[1] for g in r["idle_gaps"][1:]] == [pytest.approx(0.001)] * 2
    # the first run starts AT lo: cut by the profiler's start, not a whole one
    assert len(r["module_whole_s"]["jit_step(1)"]) == 2
    assert reduce.steps_measured(r) == pytest.approx(3.0)


def test_one_whole_and_two_half_steps_are_two_steps():
    ev = _steps(-50 * MS, 3, span=(0.0, 200 * MS))    # [-50,50] [50,150] [150,250]
    r = reduce.reduce_events(ev, 1)
    assert len(ev["devices"]["/device:TPU:0"]["modules"]) == 3      # the count of before
    assert reduce.main_module_runs(r) == pytest.approx(2.0)
    assert reduce.steps_measured(r) == pytest.approx(2.0)
    assert r["module_whole_s"] == {"jit_step(1)": [pytest.approx(0.1)]}


def test_a_slice_without_a_whole_step_prints_no_share():
    ev = _steps(-50 * MS, 2, span=(0.0, 120 * MS))    # [-50,50] [50,150]: both cut
    run = _run(ev)
    assert reduce.main_module_runs(run["trace"]) == pytest.approx(120 / 70)
    assert reduce.steps_measured(run["trace"]) is None
    assert named.roofline_share(run, 0.005, "ce_stats") is None
    assert named.host_ms_per_step(run, "train.place") is None


@pytest.mark.parametrize("phase_ms", [0.0, 35.0, 62.5])
def test_a_kernel_once_a_step_reads_the_same_share_in_a_short_and_a_long_slice(phase_ms):
    """The kernel takes 10 ms of a 100 ms step and its least time is 5 ms:
    50%, wherever the slice's ends cut the steps (35 ms: through the kernel).
    Counting the cut steps as whole read 4/3 and 11/10 of it."""
    shares = []
    for n in (3, 10):
        lo = 200 * MS + phase_ms * MS
        ev = _steps(0.0, n + 5, span=(lo, lo + n * 100 * MS))
        ev["host"] += [["train.place", i * 100 * MS, 2 * MS] for i in range(n + 5)]
        run = _run(ev)
        assert reduce.main_module_runs(run["trace"]) == pytest.approx(n)
        shares.append(named.roofline_share(run, 0.005, "ce_stats"))
        assert named.host_ms_per_step(run, "train.place") == pytest.approx(2.0)
    assert shares == [pytest.approx(50.0), pytest.approx(50.0)]


def test_a_trace_without_the_span_takes_the_device_events_extent():
    ev = _steps(40 * MS, 4)
    assert reduce.slice_interval(ev, ["/device:TPU:0"]) == (40 * MS, 440 * MS)
    r = reduce.reduce_events(ev, 1)
    assert r["window_s"] == pytest.approx(0.4) and r["busy_s"] == pytest.approx(0.4)
    # the first and the last run touch the ends: cut, two whole ones between
    assert reduce.steps_measured(r) == pytest.approx(4.0)
    assert len(r["module_whole_s"]["jit_step(1)"]) == 2
    spanned = _steps(40 * MS, 4, span=(100 * MS, 300 * MS))
    assert reduce.slice_interval(spanned, ["/device:TPU:0"]) == (100 * MS, 300 * MS)
    none = reduce.reduce_events({"devices": {}, "host": []}, 1)
    assert none["window_s"] == 0.0 and none["busy_s"] == 0.0 and none["planes"] == []


def test_four_planes_share_the_one_interval():
    ev = _steps(-50 * MS, 12, span=(0.0, 1000 * MS))
    one = ev["devices"]["/device:TPU:0"]
    ev["devices"] = {f"/device:TPU:{i}": {k: [[n, s + i * MS, d] for n, s, d in v]
                                          for k, v in one.items()} for i in range(4)}
    r = reduce.reduce_events(ev, 4)
    assert r["busy_s"] == r["window_s"] == pytest.approx(1.0)
    assert reduce.main_module_runs(r) == pytest.approx(10.0)
    assert r["op_n"][KERNEL] == pytest.approx(10.0)


def test_profile_marks_its_slice_on_the_profilers_clock():
    """`harness.Profile` through the CPU's profiler: the span is in the trace,
    the slice's length on the trace's clock is the host's to a few ms, and
    what the host did inside it lies inside it."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    profile = harness.Profile(True, t0 - 0.4, 1.0, 0.3)      # open now, for 0.3 s
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones(8)
    while profile.state != "stopping":
        profile.poll(time.perf_counter())
        with harness.annotate("train.dispatch"):
            f(x).block_until_ready()
    assert profile.host_window_s is not None
    profile.stop()
    r = profile.reduce(1)
    assert not os.path.exists(profile.dir)
    spans = [e for e in r["events"]["host"] if e[0] == reduce.SLICE_SPAN]
    assert len(spans) == 1 and r["interval_ns"] == [spans[0][1], spans[0][1] + spans[0][2]]
    assert r["window_s"] == pytest.approx(profile.host_window_s, abs=0.005)
    assert r["planes"] == [] and r["busy_s"] == 0.0            # no TPU here
    lo, hi = r["interval_ns"]
    inside = [e for e in r["events"]["host"] if e[0] == "train.dispatch" and e[1] + e[2] <= hi]
    assert inside and all(lo <= s for _, s, _ in inside)
    assert 0 < named.host_seconds(r, "train.dispatch") <= r["window_s"]
