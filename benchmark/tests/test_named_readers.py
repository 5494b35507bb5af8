"""The readers that tell a kernel or a span by the program's own name
(benchmark/named.py and the metrics new in PR 25), on two recorded traces:
`train_trace_v5e_named.json.gz`, taken on the chip from a program that names
its kernels and writes its spans onto the profiler's clock, and the older
`train_trace_v5e.json.gz`, from a program that does neither, on which every
one of them has to return None and not a wrong number."""
import gzip
import json
import os

import pytest

from benchmark import harness, named, reduce, roofline

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "train_mistral7b_seq4k"
TRACE_READERS = ["flash_fwd_roofline.train", "flash_bwd_roofline.train", "ce_stats_roofline",
                 "train_input_ms_per_step", "train_host_ms_per_step"]


def _run(trace_file: str) -> dict:
    with gzip.open(os.path.join(DATA, trace_file), "rt") as f:
        ev = json.load(f)
    reduced = reduce.reduce_events(ev, 1)
    reduced["events"] = ev
    cell = harness.load_cell(CELL)
    return {"cell": cell, "device": {"kind": "TPU v5 lite"}, "trace": reduced,
            "tokens_per_step": cell["mix"]["rows"] * cell["mix"]["seq_len"], "trace_t0": 100.0}


@pytest.fixture(scope="module")
def named_run():
    return _run("train_trace_v5e_named.json.gz")


@pytest.fixture(scope="module")
def old_run():
    return _run("train_trace_v5e.json.gz")


def _read(name, run):
    return harness.read_per_layer([name], run).get(name)


def test_a_kernel_is_told_by_its_name_or_its_metadata_never_by_an_operand():
    call = 'custom-call(bf16[4096,32768] %w), custom_call_target="tpu_custom_call"'
    assert named.is_kernel(f"%flash_fwd.3 = bf16[8] {call}", "flash_fwd")
    assert named.is_kernel(f"%flash_fwd = bf16[8] {call}", "flash_fwd")
    assert named.is_kernel(f"%flash_fwd.3.clone = bf16[8] {call}", "flash_fwd")
    assert named.is_kernel(f'%fn.7 = bf16[8] {call}, frontend_attributes={{kernel_metadata={{\n"kernel":"ce_stats"\n}}}}',
                           "ce_stats")
    assert not named.is_kernel(f"%flash_fwd_extra.3 = bf16[8] {call}", "flash_fwd")
    assert not named.is_kernel(f"%flash_dq.3 = bf16[8] {call}", "flash_fwd")
    assert not named.is_kernel(f"%jvp__.5 = bf16[8] {call}", "ce_stats")          # the head's weights do not tell
    assert not named.is_kernel("%flash_fwd.3 = bf16[8] fusion(bf16[8] %x)", "flash_fwd")   # no Pallas call


def test_every_named_kernel_of_the_step_is_in_the_recorded_trace(named_run):
    r = named_run["trace"]
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv", "ce_stats"):
        assert named.kernel_seconds(r, kernel) > 0, kernel
    # the names cut the same events as PR 24's operand shapes do
    head = reduce.dims(4096, 32768)
    assert named.kernel_seconds(r, "ce_stats") == pytest.approx(reduce.pallas_seconds(r, has=head), rel=1e-9)
    assert named.kernel_seconds(r, "flash_fwd", "flash_dq", "flash_dkv") == pytest.approx(
        reduce.pallas_seconds(r, lacks=head), rel=1e-9)
    hosts = {n for n, _, _ in named_run["trace"]["events"]["host"]}
    assert {"train.place", "train.dispatch", "train.run_ahead_wait", "train.call", "train.step"} <= hosts


# The recorded trace holds the events that START in the 0.7 s after the first device operation: two runs of
# `jit__step_fn`, the forward kernels of both steps, the backward kernels of the first only (so the shares below
# are no measurement, only arithmetic), and four calls' host spans. Durations in ns, read off the file by hand.
# It predates the `bench.slice` span, so its slice is its operations' extent, 902,334,142 ns, which a `while` of
# the second step sets: the first run of the step (2,422,781 ns in, 559,616,927 long) is whole, the second
# (from 562,055,560 on) is cut by the slice's end and counts by the part inside over the whole one's length.
FLASH_FWD_NS = 4375487 + 4362757 + 4375255 + 4362776        # %flash_fwd.2 and .3 (one a layer), twice
FLASH_BWD_NS = 7218947 + 7222278 + 6284072 + 6283402        # %flash_dkv.2/.3 and %flash_dq.2/.3, once
CE_STATS_NS = 35340708 + 35195153                           # %ce_stats.1, twice
PLACE_NS = 17500 + 11570 + 10920 + 23140                    # train.place, four calls
DISPATCH_NS = 3092560 + 2034669 + 2433271 + 2427500         # train.dispatch, four calls
STEPS = (559616927 + (902334142 - 562055560)) / 559616927    # 1.608; the two events counted as 2 up to PR 34
PEAK = 197e12                                               # bf16 operations a second of a v5e (peaks.json)
# causal attention forward of one layer: 3 rows x 4096 queries x (4096 + 1) / 2 keys x 32 heads x 128 x 4 operations
FWD_OPS = 3 * 4096 * 2048.5 * 32 * 128 * 4
HEAD_OPS = 2 * (3 * 4096) * 4096 * 32768                    # [12288, 4096] x [4096, 32768]
HAND = {
    "flash_fwd_roofline.train": 100 * STEPS * 2 * (FWD_OPS / PEAK) / (FLASH_FWD_NS / 1e9),            # 38.53
    "flash_bwd_roofline.train": 100 * STEPS * 2 * (2.5 * FWD_OPS / PEAK) / (FLASH_BWD_NS / 1e9),      # 62.32
    "ce_stats_roofline": 100 * STEPS * (HEAD_OPS / PEAK) / (CE_STATS_NS / 1e9),                       # 38.17
    "train_input_ms_per_step": PLACE_NS / 1e6 / STEPS,                                                # 0.0393
    "train_host_ms_per_step": (PLACE_NS + DISPATCH_NS) / 1e6 / STEPS,                                 # 6.25
}


@pytest.mark.parametrize("name", TRACE_READERS)
def test_reader_against_arithmetic_by_hand(name, named_run):
    assert _read(name, named_run) == pytest.approx(HAND[name], rel=1e-9)


def test_the_hand_numbers_are_what_they_were_when_checked():
    got = {k: round(v, 2) for k, v in HAND.items()}
    assert got == {"flash_fwd_roofline.train": 38.53, "flash_bwd_roofline.train": 62.32, "ce_stats_roofline": 38.17,
                   "train_input_ms_per_step": 0.04, "train_host_ms_per_step": 6.25}


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_program_that_names_nothing_reads_as_nothing(name, old_run):
    assert _read(name, old_run) is None


def test_compile_cache_misses_counts_what_was_compiled_before_the_slice(monkeypatch):
    from paddle_tpu.core import compile_cache

    log = [{"fun_name": "jit(a)", "t0": 1.0, "cache": "hit"}, {"fun_name": "jit(b)", "t0": 2.0, "cache": "miss"},
           {"fun_name": "jit(c)", "t0": 3.0, "cache": "unstored"}, {"fun_name": "jit(d)", "t0": 4.0, "cache": "off"},
           {"fun_name": "jit(ref)", "t0": 200.0, "cache": "miss"}]     # the reference's, after the window
    monkeypatch.setattr(compile_cache, "compile_log", lambda: list(log))
    assert _read("compile_cache_misses", {"trace_t0": 100.0}) == 2.0
    assert _read("compile_cache_misses", {"trace_t0": 2.5}) == 1.0
    assert _read("compile_cache_misses", {"trace_t0": None}) is None       # no traced slice
    monkeypatch.setattr(compile_cache, "compile_log", lambda: [])
    assert _read("compile_cache_misses", {"trace_t0": 100.0}) is None      # nobody listened
    monkeypatch.delattr(compile_cache, "compile_log")
    assert _read("compile_cache_misses", {"trace_t0": 100.0}) is None      # a program without a compile log


def test_serving_readers_on_hand_made_spans():
    def span(name, ts, dur):
        return {"name": name, "ts": ts, "dur": dur}

    spans = [span("engine.submit_wait", 0, 1000.0), span("engine.submit_wait", 10, 3000.0),
             span("engine.submit_wait", 20, 2000.0)]
    for step, (pack, disp, read, apply) in enumerate([(100.0, 400.0, 9000.0, 500.0), (200.0, 600.0, 9000.0, 200.0),
                                                      (100.0, 500.0, 9000.0, 300.0)]):
        t = 1e5 * (step + 1)
        spans += [span("engine.decode_step", t, 11000.0), span("engine.decode.pack", t, pack),
                  span("engine.decode.dispatch", t + 1000, disp), span("engine.decode.readback", t + 2000, read),
                  span("engine.decode.apply", t + 11000, apply)]
    run = {"spans": spans}
    assert _read("engine_submit_wait_ms", run) == pytest.approx(2.9)        # p95 of 1, 2, 3 ms
    assert _read("engine_decode_host_ms", run) == pytest.approx(1.0)        # median of 1.0, 1.0, 0.9 ms
    assert _read("engine_submit_wait_ms", {"spans": []}) is None
    assert _read("engine_decode_host_ms", {"spans": []}) is None
