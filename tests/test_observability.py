"""Unified observability plane (docs/observability.md), tier-1 core:
metrics registry (Prometheus exposition golden text, concurrent-update
exactness, collectors), cross-component tracing (trace-id propagation
router -> replica -> scheduler/engine asserted on a two-replica in-process
run), honest step telemetry (bit-equal losses with collection on,
cost_analysis FLOPs), LogWriter durability, the structured event journal,
and the /metrics HTTP endpoint + zero-retrace guard on a real engine."""
import contextlib
import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing as obs_tracing
from paddle_tpu.observability.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_prometheus_exposition_golden(self):
        """The exact text-format 0.0.4 output — HELP/TYPE lines, label
        rendering + escaping, histogram cumulative buckets with the +Inf
        terminal, _sum/_count ordering, trailing newline."""
        r = MetricsRegistry()
        c = r.counter("http_requests_total", "total requests",
                      labels=("route", "code"))
        c.labels(route="/generate", code="200").inc(3)
        c.labels(route='/we"ird\npath', code="503").inc()
        r.gauge("queue_depth", "waiting requests").set(7)
        h = r.histogram("latency_ms", "per-token latency",
                        buckets=(1, 5, 10))
        for v in (0.5, 3.0, 7.0, 100.0):
            h.observe(v)
        expected = "\n".join([
            '# HELP http_requests_total total requests',
            '# TYPE http_requests_total counter',
            'http_requests_total{code="200",route="/generate"} 3',
            'http_requests_total{code="503",route="/we\\"ird\\npath"} 1',
            '# HELP latency_ms per-token latency',
            '# TYPE latency_ms histogram',
            'latency_ms_bucket{le="1"} 1',
            'latency_ms_bucket{le="5"} 2',
            'latency_ms_bucket{le="10"} 3',
            'latency_ms_bucket{le="+Inf"} 4',
            'latency_ms_sum 110.5',
            'latency_ms_count 4',
            '# HELP queue_depth waiting requests',
            '# TYPE queue_depth gauge',
            'queue_depth 7',
        ]) + "\n"
        assert r.prometheus_text() == expected

    def test_type_and_label_conflicts_raise(self):
        r = MetricsRegistry()
        r.counter("x_total", "c")
        with pytest.raises(TypeError):
            r.gauge("x_total", "g")
        g = r.gauge("g", "g", labels=("a",))
        with pytest.raises(ValueError):
            g.labels(b="1")
        with pytest.raises(ValueError):
            r.counter("neg", "c").inc(-1)

    def test_concurrent_updates_exact(self):
        """Lock-striped updates lose nothing: N threads hammering shared
        counter/histogram children produce exact totals."""
        r = MetricsRegistry()
        c = r.counter("ops_total", "", labels=("worker",))
        h = r.histogram("obs_ms", "", buckets=(1, 10, 100))
        g = r.gauge("acc", "")
        N_THREADS, N_OPS = 8, 2000
        barrier = threading.Barrier(N_THREADS)

        def work(i):
            child = c.labels(worker=str(i % 2))  # 2 shared children
            barrier.wait()
            for k in range(N_OPS):
                child.inc()
                h.observe(float(k % 150))
                g.inc(1.0)

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(N_THREADS)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = sum(child.value for _, child in c.samples())
        assert total == N_THREADS * N_OPS
        hc = h._default_child()
        assert hc.count == N_THREADS * N_OPS
        assert g.value == N_THREADS * N_OPS
        # cumulative buckets are consistent: monotonic, terminal == count
        cum = hc.cumulative()
        assert [n for _, n in cum] == sorted(n for _, n in cum)
        assert cum[-1][1] == hc.count

    def test_histogram_quantiles(self):
        r = MetricsRegistry()
        h = r.histogram("lat", "", buckets=(10, 20, 50, 100))
        for v in range(100):  # uniform 0..99
            h.observe(float(v))
        assert 40 <= h.quantile(0.5) <= 60
        assert h.quantile(0.99) >= 90

    def test_collector_weakref_owner(self):
        r = MetricsRegistry()

        class Owner:
            pass

        owner = Owner()
        calls = []
        r.add_collector(lambda reg: calls.append(1), owner=owner)
        r.snapshot()
        assert calls == [1]
        del owner
        import gc

        gc.collect()
        r.snapshot()
        assert calls == [1]  # dead-owner collector dropped, not called

    def test_snapshot_json_safe_and_export_jsonl(self, tmp_path):
        from paddle_tpu.utils.log_writer import LogReader, LogWriter

        r = MetricsRegistry()
        r.gauge("train_loss", "").set(1.5)
        h = r.histogram("lat", "", buckets=(1.0,))
        h.observe(0.5)
        h.observe(2.0)
        snap = r.snapshot()
        json.loads(json.dumps(snap))  # +Inf bucket must serialize strictly
        assert snap["lat"]["samples"][0]["buckets"][-1][0] == "+Inf"
        with LogWriter(str(tmp_path)) as w:
            r.export_jsonl(w, step=3)
        reader = LogReader(str(tmp_path))
        assert reader.scalars("train_loss") == [(3, 1.5)]
        (step, text), = reader.texts("lat")
        assert step == 3 and json.loads(text)["count"] == 2

    def test_counter_mirror_reset_semantics(self):
        r = MetricsRegistry()
        c = r.counter("m_total", "")
        child = c._default_child()
        child._set_total(10)
        child._set_total(3)  # source reset (Prometheus counter reset)
        assert c.value == 3


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
class TestTracing:
    def test_span_and_context_inheritance(self):
        obs_tracing.start_tracing()
        try:
            with obs_tracing.span("outer", component="router",
                                  trace_id="t123"):
                with obs_tracing.span("inner", component="engine"):
                    pass
        finally:
            evs = obs_tracing.stop_tracing()
        by_name = {e["name"]: e for e in evs}
        assert by_name["outer"]["args"]["trace_id"] == "t123"
        assert by_name["inner"]["args"]["trace_id"] == "t123"  # inherited
        assert by_name["inner"]["args"]["component"] == "engine"
        assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]

    def test_record_event_is_a_span_of_the_one_store(self):
        from paddle_tpu.profiler import RecordEvent

        obs_tracing.start_tracing()
        try:
            with obs_tracing.trace_context("abc"):
                with RecordEvent("train.place", attrs={"k": 1}):
                    pass
                ev2 = RecordEvent("explicit")
                ev2.end()            # end() without begin() records nothing
                ev2.begin()
                ev2.end()
        finally:
            evs = obs_tracing.stop_tracing()
        (ev,) = [e for e in evs if e["name"] == "train.place"]
        assert ev["args"]["trace_id"] == "abc" and ev["args"]["k"] == 1
        assert [e["name"] for e in evs].count("explicit") == 1

    def test_profiler_reads_the_one_store(self):
        """No second collector: a Profiler opens a window of the tracing
        store, or reads its slice of one that is open and leaves it open."""
        import paddle_tpu.profiler as profiler

        assert not hasattr(profiler, "_Collector")
        assert not hasattr(profiler, "_collector")
        assert not hasattr(obs_tracing, "_device_trace_events")
        with profiler.Profiler() as prof:
            assert obs_tracing.collecting()
            with obs_tracing.span("by_span"):
                with profiler.RecordEvent("by_record_event"):
                    pass
        assert not obs_tracing.collecting()
        assert [e["name"] for e in prof._events] == ["by_record_event",
                                                     "by_span"]
        assert "by_span" in prof.summary()
        obs_tracing.start_tracing()
        with obs_tracing.span("before"):
            pass
        with profiler.Profiler() as inner:
            with obs_tracing.span("inside"):
                pass
        assert obs_tracing.collecting()          # not the profiler's to close
        assert [e["name"] for e in inner._events] == ["inside"]
        assert [e["name"] for e in obs_tracing.stop_tracing()] == [
            "before", "inside"]

    def test_span_names_its_enclosing_span(self):
        """args.parent: a layer's self time is its span less its
        children. record_span (measured by the caller) gets it too; an
        unbound span carries one and is nobody's parent."""
        obs_tracing.start_tracing()
        try:
            with obs_tracing.span("outer"):
                with obs_tracing.span("mid", bind=False):
                    with obs_tracing.span("inner"):
                        pass
                obs_tracing.record_span("measured", time.perf_counter_ns(),
                                        1000, {"component": "c"})
            with obs_tracing.span("alone"):
                pass
        finally:
            evs = {e["name"]: e for e in obs_tracing.stop_tracing()}
        assert "parent" not in evs["outer"]["args"]
        assert evs["mid"]["args"]["parent"] == "outer"
        assert evs["inner"]["args"]["parent"] == "outer"
        assert evs["measured"]["args"]["parent"] == "outer"
        assert "parent" not in evs["alone"]["args"]

    def test_span_cost_with_everything_off(self):
        """Neither a collection window nor a profiler session: a span is
        two checks and costs under 2 us (median of 10,000)."""
        assert not obs_tracing.tracing_active()
        clock = time.perf_counter_ns
        costs = []
        for _ in range(10_000):
            t0 = clock()
            with obs_tracing.span("off", component="c"):
                pass
            costs.append(clock() - t0)
        costs.sort()
        assert costs[len(costs) // 2] < 2000, costs[len(costs) // 2]
        assert obs_tracing.events_snapshot() == []

    def test_unbound_span_leaves_thread_context_alone(self):
        """bind=False (the generator-wrapping mode the router uses): two
        interleaved generator spans on one thread must neither leak their
        trace id into the thread context nor restore it non-LIFO."""
        obs_tracing.start_tracing()
        try:
            def gen(tid):
                with obs_tracing.span("router.stream", component="router",
                                      trace_id=tid, bind=False):
                    yield 1
                    yield 2

            a, b = gen("ta"), gen("tb")
            next(a)
            next(b)
            assert obs_tracing.current_trace_id() is None
            a.close()        # finishes A while B is still live
            assert obs_tracing.current_trace_id() is None
            b.close()
            assert obs_tracing.current_trace_id() is None
        finally:
            evs = obs_tracing.stop_tracing()
        assert {e["args"]["trace_id"] for e in evs} == {"ta", "tb"}

    def test_inactive_tracing_records_nothing(self):
        with obs_tracing.span("x", component="c"):
            pass
        assert obs_tracing.events_snapshot() == []

    def test_export_chrome(self, tmp_path):
        obs_tracing.start_tracing()
        with obs_tracing.span("a", component="c"):
            pass
        obs_tracing.stop_tracing()
        path = str(tmp_path / "trace.json")
        summary = obs_tracing.export_chrome(
            path, extra_events=[{"name": "dev", "ph": "X", "ts": 0,
                                 "dur": 1, "pid": 9, "tid": 9}])
        assert summary == {"host_events": 1, "path": path}
        with pytest.raises(TypeError):      # merged nothing, and went
            obs_tracing.export_chrome(path, device_trace_dir=str(tmp_path))
        with open(path) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"]}
        assert names == {"a", "dev"}


# ---------------------------------------------------------------------------
# the profiler's clock: spans, kernel names, the compile log
# ---------------------------------------------------------------------------
def _host_events(trace_dir):
    """{name: [stats dict, ...]} of `/host:CPU` in the newest xplane."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    (plane,) = [p for p in data.planes if p.name == "/host:CPU"]
    out = {}
    for line in plane.lines:
        for e in line.events:
            out.setdefault(e.name, []).append(
                (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


class TestProfilerClock:
    def test_spans_reach_the_xplane_by_themselves(self, tmp_path):
        """Under a jax.profiler session, and WITHOUT start_tracing(), a
        span is a TraceAnnotation of /host:CPU: scalar attributes are its
        stats, lists stay out, and a span measured by the caller leaves a
        marker carrying its length."""
        import jax

        assert not obs_tracing.tracing_active()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert obs_tracing.tracing_active()
            assert not obs_tracing.collecting()
            with obs_tracing.span("engine.t_outer", component="engine",
                                  rid=7, trace_ids=["a", "b"]):
                with obs_tracing.span("engine.t_inner"):
                    time.sleep(0.002)
            obs_tracing.record_span("scheduler.t_wait",
                                    time.perf_counter_ns(), 5_000_000,
                                    {"component": "scheduler", "rid": 7})
        finally:
            jax.profiler.stop_trace()
        assert not obs_tracing.tracing_active()
        assert obs_tracing.events_snapshot() == []   # no window was open
        evs = _host_events(str(tmp_path))
        (outer,), (inner,) = evs["engine.t_outer"], evs["engine.t_inner"]
        assert outer[2]["component"] == "engine" and outer[2]["rid"] == 7
        assert "trace_ids" not in outer[2]
        assert outer[0] <= inner[0]
        assert inner[0] + inner[1] <= outer[0] + outer[1]
        assert inner[1] >= 2e6
        (wait,) = evs["scheduler.t_wait"]
        assert wait[2]["dur_us"] == 5000

    def test_train_step_spans_and_bit_equal_loss(self, tmp_path):
        """A tiny CompiledTrainStep under a profiler session leaves its own
        spans in /host:CPU without start_tracing(); the session changes
        neither the loss (bit-equal) nor the number of traces (one)."""
        import jax

        from paddle_tpu.core.compile_cache import start_compile_log

        start_compile_log()
        st_plain, ids = _tiny_step(False, seed=3)
        st_prof, _ = _tiny_step(False, seed=3)
        plain = [float(st_plain(ids, ids, ids)) for _ in range(3)]
        jax.profiler.start_trace(str(tmp_path))
        obs_tracing.start_tracing()
        try:
            prof = [float(st_prof(ids, ids, ids)) for _ in range(3)]
        finally:
            window = obs_tracing.stop_tracing()
            obs_tracing.reset()
            jax.profiler.stop_trace()
        assert plain == prof
        assert st_plain._jitted._cache_size() == 1
        assert st_prof._jitted._cache_size() == 1
        evs = _host_events(str(tmp_path))
        for name in ("train.place", "train.dispatch",
                     "train.run_ahead_wait", "train.call"):
            assert len(evs[name]) == 3, (name, sorted(evs))
        # the program, its compile, the table of its parts
        assert len(evs["train.build"]) == 3
        assert [e[2]["step"] for e in evs["train.dispatch"]] == [1, 2, 3]
        assert [e[2]["step_num"] for e in evs["train.call"]] == [1, 2, 3]
        # every part lies inside its step's train.call
        calls = sorted(evs["train.call"])
        for name in ("train.place", "train.dispatch",
                     "train.run_ahead_wait"):
            for (s, d, _), (cs, cd, _) in zip(sorted(evs[name]), calls):
                assert cs <= s and s + d <= cs + cd
        c = st_prof.host_counters()
        assert c["steps"] == 3 and c["builds"] == 1
        # the host's seconds by part are the spans' own, read in the window:
        # the first dispatch holds the build's compile
        durs = {}
        for e in window:
            durs.setdefault(e["name"], []).append(e["dur"])
        first, *later = durs["train.dispatch"]
        assert first > max(later) > 0 and min(durs["train.build"]) > 0
        assert min(durs["train.place"]) > 0
        assert min(durs["train.run_ahead_wait"]) >= 0
        # the compile log knows the step's program by JAX's name for it
        assert c["compile"]["programs"] >= 2 and c["compile"]["secs"] > 0

    def test_scopes_name_the_steps_operations(self):
        """jax.named_scope in the step and the model: metadata only, and
        every scope the docs promise is in the lowered program."""
        st, ids = _tiny_step(False, seed=4)
        st(ids, ids, ids)
        text = st._lowered.as_text(debug_info=True)
        for scope in ("loss", "optimizer", "embed", "attn", "mlp", "head"):
            assert f"/{scope}/" in text or f"({scope})" in text, scope
        assert "transpose(jvp(loss))" in text


def _pallas_names(fn, *args):
    """(name, metadata) of every pallas_call in the jaxpr of fn(*args)."""
    import jax
    from jax._src import core

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              dict(eqn.params["metadata"] or {})))
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _kernel_cases():
    """One call of each public kernel of ops/pallas (interpret mode), with
    the names its pallas_calls must carry."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa, ce, pa, gm, rk, kda, mr = (
        importlib.import_module("paddle_tpu.ops.pallas." + m)
        for m in ("flash_attention", "fused_ce", "paged_attention",
                  "grouped_matmul", "rmsnorm_kernel", "kda", "moe_rows"))
    f32, i32 = jnp.float32, jnp.int32

    def flash(q):
        with fa.force_interpret():
            return jax.grad(lambda q: fa.flash_attention_bshd(
                q, q, q, causal=True).sum())(q)

    def paged(q, kv, table, lens):
        with pa.force_interpret():
            return pa.paged_decode_attention(q, kv, kv, table, lens)

    def paged_count(lens):
        with pa.force_interpret():
            return pa.page_visit_counts(lens, 16, 4)

    # 32 tokens x 4 pairs, the first 16 pairs in the first 16 of 32 buffer rows
    pairs = jnp.arange(128, dtype=i32)
    layout = mr.rows_layout(jnp.where(jnp.arange(32) < 16, jnp.arange(32), 128).astype(i32),
                            jnp.where(pairs < 16, pairs, 32).reshape(32, 4), 1, 16)

    def movers(x, y, w):
        with fa.force_interpret():
            return jax.grad(lambda x, y, w: (
                mr.rows_gather(x, layout, block_rows=16).sum()
                + mr.rows_combine(y, w, layout, block_rows=16).sum()),
                argnums=(0, 1, 2))(x, y, w)

    def mover_counts():
        with fa.force_interpret():
            return (mr.rows_gather_visit_counts(layout, 16),
                    mr.rows_combine_visit_counts(layout))

    lab = jnp.zeros((32,), i32)
    gids = jnp.repeat(jnp.arange(2, dtype=i32), 16)
    lens = jnp.array([5, 9], i32)
    return {
        "flash_attention": (
            flash, (jnp.ones((1, 128, 2, 64), f32),),
            ["flash_fwd", "flash_dq", "flash_dkv"]),
        "flash_block_count": (
            lambda s: fa.segment_block_visit_counts(s, interpret=True),
            (jnp.zeros((1, 256), i32),), ["flash_block_count"]),
        "fused_ce": (
            lambda x, w: jax.grad(
                lambda x: ce.fused_linear_cross_entropy_loss(
                    x, w, lab, variant="pallas").sum())(x),
            (jnp.ones((32, 128), f32), jnp.ones((128, 256), f32)),
            ["ce_stats"]),
        "paged_decode": (
            paged, (jnp.ones((2, 2, 64), f32), jnp.ones((2, 8, 16, 64), f32),
                    jnp.zeros((2, 4), i32), lens), ["paged_decode"]),
        "paged_block_count": (paged_count, (lens,), ["paged_block_count"]),
        "rmsnorm": (
            lambda x, w: jax.grad(lambda x: rk.rmsnorm(x, w).sum())(x),
            (jnp.ones((16, 128), f32), jnp.ones((128,), f32)),
            ["rmsnorm_fwd", "rmsnorm_bwd"]),
        "grouped_matmul": (
            lambda x, w: jax.grad(
                lambda x, w: gm.grouped_matmul(
                    x, w, gids, block_rows=16, backend="pallas").sum(),
                argnums=(0, 1))(x, w),
            (jnp.ones((32, 128), f32), jnp.ones((2, 128, 128), f32)),
            ["grouped_matmul", "grouped_matmul", "grouped_matmul_dw"]),
        "grouped_matmul_block_count": (
            lambda g: gm.grouped_matmul_visit_counts(g, 2, 16, True),
            (gids,), ["grouped_matmul_block_count"]),
        "kda": (
            lambda q, g, b: jax.grad(lambda q: kda.kda_chunked(
                q, q, q, g, b, interpret=True).sum())(q),
            (jnp.ones((1, 64, 2, 128), f32), -jnp.ones((1, 64, 2, 128), f32),
             jnp.ones((1, 64, 2), f32) * 0.5),
            ["kda_chunk_fwd", "kda_fwd", "kda_chunk_fwd", "kda_fwd", "kda_bwd", "kda_chunk_bwd"]),
        "moe_rows": (
            movers, (jnp.ones((32, 128), f32), jnp.ones((32, 128), f32),
                     jnp.ones((32, 4), f32)),
            ["moe_rows_gather", "moe_rows_combine", "moe_rows_gather",
             "moe_rows_combine"]),
        "moe_rows_block_count": (
            mover_counts, (),
            ["moe_rows_gather_block_count", "moe_rows_combine_block_count"]),
    }


class TestKernelNames:
    @pytest.mark.parametrize("case", [
        "flash_attention", "flash_block_count", "fused_ce", "paged_decode",
        "paged_block_count", "rmsnorm", "grouped_matmul",
        "grouped_matmul_block_count", "kda", "moe_rows", "moe_rows_block_count"])
    def test_every_pallas_call_is_named(self, case):
        fn, args, want = _kernel_cases()[case]
        found = _pallas_names(fn, *args)
        assert [n for n, _ in found] == want
        assert all(n and meta == {"kernel": n} for n, meta in found)

    def test_no_call_site_is_left_out(self):
        """Every pl.pallas_call( of ops/pallas passes a kernel_name: the
        cases above cover each file, this counts the call sites."""
        import re

        root = os.path.join(os.path.dirname(paddle.__file__), "ops", "pallas")
        calls = names = 0
        for f in sorted(os.listdir(root)):
            if f.endswith(".py"):
                src = open(os.path.join(root, f)).read()
                calls += len(re.findall(r"pl\.pallas_call\(", src))
                names += len(re.findall(r"\*\*_compat\.kernel_name\(", src))
        assert calls == names == 19       # the KDA chunk kernels, forward and backward


@contextlib.contextmanager
def _temporary_persistent_cache(path):
    """JAX's persistent cache at `path`, storing every program however
    small or quick, for the length of the block."""
    import jax
    from jax._src import compilation_cache

    keys = {"jax_compilation_cache_dir": str(path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


class TestCompileLog:
    def test_hit_and_miss_land_on_the_right_program(self, tmp_path):
        """With a temporary persistent cache: the first compile of a
        program is a 'miss' (compiled and written), the second a 'hit',
        each under its own fun_name with its phases' seconds."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core import compile_cache as cc

        def alpha():
            # the same program from a new function: JAX's in-memory cache
            # does not know it, the persistent cache does
            def log_alpha(x):
                return x * 2 + 1
            return log_alpha

        def log_beta(x):
            return x - 3

        cc.start_compile_log()
        cc.start_compile_log()                   # registers once
        n0 = len(cc.compile_log())
        t_before = time.perf_counter()
        with _temporary_persistent_cache(tmp_path):
            x = jnp.ones(3, jnp.float32)
            jax.jit(alpha())(x)
            jax.jit(log_beta)(x)
            jax.jit(alpha())(x)
        mine = [e for e in cc.compile_log()[n0:] if "log_" in e["fun_name"]]
        assert [(e["fun_name"], e["cache"]) for e in mine] == [
            ("jit(log_alpha)", "miss"), ("jit(log_beta)", "miss"),
            ("jit(log_alpha)", "hit")]
        for e in mine:
            assert t_before < e["t0"] < time.perf_counter()
            assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["compile_s"] > 0
        tot = cc.compile_totals("jit(log_alpha)")
        assert (tot["programs"], tot["hits"], tot["misses"]) == (2, 1, 1)
        assert tot["secs"] == pytest.approx(sum(
            e["trace_s"] + e["lower_s"] + e["compile_s"]
            for e in mine if e["fun_name"] == "jit(log_alpha)"))
        assert cc.compile_totals()["traces"] >= 3

    def test_a_trace_cache_lookup_opens_no_program(self):
        """A call that finds its trace in JAX's trace cache (a function
        lowered before its first call) reports a trace and compiles nothing:
        the next program's entry starts at its own trace, with its own
        seconds."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core import compile_cache as cc

        def log_gamma(x):
            return x * 3

        def log_delta(x):
            return x + 5

        cc.start_compile_log()
        x = jnp.ones(3, jnp.float32)
        f = jax.jit(log_gamma)
        f.lower(x).compile()
        time.sleep(0.2)
        f(x)                               # the lookup: a trace event only
        time.sleep(0.2)
        t_before = time.perf_counter()
        jax.jit(log_delta)(x)
        (e,) = [e for e in cc.compile_log() if e["fun_name"] == "jit(log_delta)"]
        assert t_before < e["t0"] < time.perf_counter()
        assert e["trace_s"] + e["lower_s"] + e["compile_s"] < (
            time.perf_counter() - t_before)

    def test_registry_series_are_the_persistent_caches_log(self, tmp_path):
        """`compile_cache_hits_total` / `_misses_total` are published from
        the compile log of the cache that is on (JAX's persistent one), at
        scrape time: after a miss-then-hit run they equal the log's own
        hits and misses."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core import compile_cache as cc

        def gamma():
            def log_gamma(x):
                return x * 5 - 2
            return log_gamma

        cc.start_compile_log()

        def series():
            snap = obs_metrics.registry().snapshot()
            return tuple(snap[n]["samples"][0]["value"] for n in (
                "compile_cache_hits_total", "compile_cache_misses_total"))

        h0, m0 = series()
        with _temporary_persistent_cache(tmp_path):
            x = jnp.ones(3, jnp.float32)
            jax.jit(gamma())(x)
            jax.jit(gamma())(x)
        tot = cc.compile_totals()
        assert series() == (tot["hits"], tot["misses"])
        assert series() == (h0 + 1, m0 + 1)


# ---------------------------------------------------------------------------
# event journal
# ---------------------------------------------------------------------------
class TestEventJournal:
    def test_schema_and_sinks(self, tmp_path):
        from paddle_tpu.observability.events import EventJournal

        j = EventJournal(maxlen=4)
        path = str(tmp_path / "events.jsonl")
        j.attach(path)
        rec = j.emit("router", "circuit_open", severity="error", replica=2)
        assert set(("ts", "component", "event", "severity")) <= set(rec)
        with pytest.raises(ValueError):
            j.emit("x", "y", severity="fatal")
        with pytest.raises(ValueError):
            j.emit("x", "y", ts=123.0)   # schema fields are reserved
        for i in range(6):
            j.emit("serving", "page_eviction", rid=i)
        assert len(j.recent()) == 4                       # bounded ring
        assert j.recent(component="router") == []         # rotated out
        assert j.emitted == 7
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip()]
        assert len(lines) == 7                            # sink keeps all
        assert lines[0]["event"] == "circuit_open"
        j.close()

    def test_broken_sink_never_crashes_the_emitter(self, tmp_path):
        """Journal emits sit on recovery paths (rollback incidents) and
        under component locks: a full-disk/closed sink must be recorded,
        not raised."""
        from paddle_tpu.observability.events import EventJournal

        j = EventJournal()
        path = str(tmp_path / "e.jsonl")
        j.attach(path)
        j._files[path].close()               # simulate a dead sink
        with pytest.warns(UserWarning, match="journal sink failed"):
            rec = j.emit("resilience", "rollback", severity="warn", step=1)
        assert rec["event"] == "rollback"
        assert j.recent(event="rollback")    # ring still has it
        assert j.sink_errors
        j.emit("resilience", "rollback", step=2)   # warns once, never raises

    def test_help_text_escaping_keeps_quotes_literal(self):
        r = MetricsRegistry()
        r.gauge("g", 'the "p99" gate\nline2').set(1)
        text = r.prometheus_text()
        assert '# HELP g the "p99" gate\\nline2' in text

    def test_emit_feeds_metrics_counter(self):
        before = obs_events.journal().emitted
        obs_events.emit("testcomp", "tick")
        reg = obs_metrics.registry()
        c = reg.counter("events_total", "", labels=("component", "event"))
        assert c.labels(component="testcomp", event="tick").value >= 1
        assert obs_events.journal().emitted == before + 1

    def test_incident_log_bridges_to_journal(self, tmp_path):
        from paddle_tpu.distributed.resilience.supervisor import IncidentLog

        log = IncidentLog()
        log.emit("rollback", step=7, cause="anomaly:nan")
        recent = obs_events.journal().recent(component="resilience",
                                             event="rollback")
        assert recent and recent[-1]["step"] == 7
        assert recent[-1]["severity"] == "warn"


# ---------------------------------------------------------------------------
# LogWriter durability satellites
# ---------------------------------------------------------------------------
class TestLogWriterDurability:
    def test_atexit_flush_covers_unflushed_writers(self, tmp_path):
        from paddle_tpu.utils import log_writer as lw

        w = lw.LogWriter(str(tmp_path), max_queue=10_000, flush_secs=10_000)
        w.add_scalar("loss", 1.0, 0)
        # buffered: nothing on disk yet (large queue + flush interval)
        assert os.path.getsize(w._path) == 0
        lw._flush_live_writers()   # what the atexit hook runs
        assert os.path.getsize(w._path) > 0
        w.close()
        w.close()                  # idempotent
        assert w not in lw._LIVE_WRITERS

    def test_reader_last_and_texts(self, tmp_path):
        from paddle_tpu.utils.log_writer import LogReader, LogWriter

        with LogWriter(str(tmp_path)) as w:
            w.add_scalar("loss", 3.0, 1)
            w.add_scalar("loss", 2.0, 5)
            w.add_text("note", "hello", 2)
        r = LogReader(str(tmp_path))
        assert r.last("loss") == (5, 2.0)
        assert r.last("missing") is None
        assert r.texts("note") == [(2, "hello")]


# ---------------------------------------------------------------------------
# honest step telemetry
# ---------------------------------------------------------------------------
def _tiny_step(collect, seed=0):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.parallel import CompiledTrainStep

    cfg = llama_tiny_config(num_hidden_layers=2, vocab_size=128,
                            hidden_size=32, intermediate_size=64,
                            max_position_embeddings=32)
    paddle.seed(seed)
    m = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    st = CompiledTrainStep(m, lambda o, l: o, opt, collect_metrics=collect,
                           metrics_every=0)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (4, 32)).astype(np.int64))
    return st, ids


# ONE telemetry-on and ONE telemetry-off compiled step shared by the
# class (each CompiledTrainStep costs a full XLA compile; tier-1 runs at
# its wall-clock budget). Tests only step them FORWARD — assertions are
# relative to step_count, never absolute state.
@pytest.fixture(scope="module")
def tele_steps():
    st_off, ids = _tiny_step(False)
    st_on, _ = _tiny_step(True)
    return st_off, st_on, ids


class TestStepTelemetry:
    def test_losses_bit_equal_and_metrics_settle(self, tele_steps):
        st_off, st_on, ids = tele_steps
        for _ in range(4):
            l_off = st_off(ids, ids, ids)
            l_on = st_on(ids, ids, ids)
        st_off.drain()
        st_on.drain()
        assert float(l_off) == float(l_on)   # telemetry cannot move the math
        md = st_on.last_metrics()
        assert md is not None
        assert md["step"] == st_on.step_count
        assert md["loss"] == float(l_on)
        assert md["grad_norm"] > 0 and np.isfinite(md["grad_norm"])
        assert md["skipped"] == 0.0
        assert "host_step_ms" in md
        assert st_off.last_metrics() is None  # off = no collection at all

    def test_async_runahead_not_broken_by_collection(self, tele_steps):
        _, st, ids = tele_steps
        futures = [st.step_async(ids, ids, ids) for _ in range(4)]
        st.drain()
        assert all(np.isfinite(float(f)) for f in futures)
        assert st.last_metrics()["step"] == st.step_count
        assert st._pending_metrics == []      # drain settles everything

    def test_cost_analysis_flops(self, tele_steps):
        from paddle_tpu.models.llama import LlamaForCausalLM, \
            llama_tiny_config
        from paddle_tpu.parallel import CompiledTrainStep

        fresh = CompiledTrainStep(
            LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1)),
            lambda o, l: o, collect_metrics=True)
        with pytest.raises(RuntimeError):
            fresh.cost_analysis()             # needs one executed step
        _, st, ids = tele_steps
        st(ids, ids, ids)
        st.drain()
        flops = st.flops_per_step()
        assert flops > 0
        assert st.cost_analysis() is st.cost_analysis()   # cached

    def test_metrics_callback_streams_to_registry_and_jsonl(
            self, tele_steps, tmp_path):
        from paddle_tpu.hapi import MetricsCallback
        from paddle_tpu.utils.log_writer import LogReader

        _, st, ids = tele_steps

        class FakeDist:
            _step = st

        class FakeModel:
            _dist_model = FakeDist()

        reg = MetricsRegistry()
        cb = MetricsCallback(logdir=str(tmp_path), registry=reg,
                             peak_flops_per_s=1e12)
        cb.set_model(FakeModel())
        cb.on_train_begin()
        for i in range(3):
            loss = st(ids, ids, ids)
            st.drain()
            cb.on_train_batch_end(i, {"loss": float(loss)})
        cb.on_train_end()
        snap = reg.snapshot()
        assert snap["train_steps_total"]["samples"][0]["value"] == 3
        assert snap["train_loss"]["samples"][0]["value"] == float(loss)
        assert snap["train_grad_norm"]["samples"][0]["value"] > 0
        # the MFU gauge derives from compiled.cost_analysis() FLOPs
        assert 0 < snap["train_mfu"]["samples"][0]["value"] < 1e6
        series = LogReader(str(tmp_path)).scalars("train/loss")
        assert len(series) == 3


# ---------------------------------------------------------------------------
# trace-id propagation on a two-replica in-process run
# ---------------------------------------------------------------------------
# the table of named parts a built step publishes (observability.scopes)
# ---------------------------------------------------------------------------

def _experts_step(build_table=True):
    """A tiny latent-attention model with held and shared experts (kernels
    interpreted), one step built and run."""
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                               deepseek_v3_tiny_config)
    from paddle_tpu.ops.pallas.flash_attention import force_interpret
    from paddle_tpu.parallel import CompiledTrainStep

    with force_interpret():
        paddle.seed(1)
        model = DeepseekV3ForCausalLM(
            deepseek_v3_tiny_config(router_bias_update_rate=0.01))
        model.train()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters(),
                                     multi_precision=True)
        st = CompiledTrainStep(model, lambda out, lab: out, optimizer=opt,
                               collect_metrics=True)
        if not build_table:
            st._compile = lambda args: None
        b = np.random.RandomState(5).randint(0, 128, (2, 129)).astype(np.int32)
        ids, lab = paddle.to_tensor(b[:, :-1]), paddle.to_tensor(b[:, 1:])
        losses = [float(st(ids, lab, lab)) for _ in range(2)]
    return st, losses


@pytest.fixture(scope="module")
def scope_tables():
    """A dense and a held-experts step, each with the table it published
    and the number of `jit(_step_fn)` compiles its build logged."""
    from paddle_tpu.core.compile_cache import compile_log, start_compile_log
    from paddle_tpu.observability import scopes

    start_compile_log()
    out = {}

    def step_compiles():
        return sum(e["fun_name"] == "jit(_step_fn)" for e in compile_log())

    before = step_compiles()
    dense, ids = _tiny_step(False, seed=6)
    dense_losses = [float(dense(ids, ids, ids)) for _ in range(2)]
    out["dense"] = (dense, scopes.last_table(), step_compiles() - before,
                    dense_losses)
    before = step_compiles()
    experts, losses = _experts_step()
    out["experts"] = (experts, scopes.last_table(), step_compiles() - before,
                      losses)
    return out


class TestScopeTable:
    def test_each_step_publishes_its_table(self, scope_tables):
        for kind, (st, table, _, _) in scope_tables.items():
            assert table["module"] == "jit__step_fn", kind
            assert table["ops"], kind
            # every instruction the executable runs at the top level is in it
            text = st._executable.as_text()
            entry = [ln for ln in text.splitlines() if ln.startswith("ENTRY")]
            assert len(entry) == 1

    def test_every_named_instruction_is_a_listed_part(self, scope_tables):
        from paddle_tpu.observability import scopes

        for kind, (st, table, _, _) in scope_tables.items():
            _, entry, comps = scopes._computations(st._executable.as_text())
            named = 0
            for comp in comps.values():
                for name, _, rest in comp:
                    if name not in table["ops"]:
                        continue
                    _, op_name, _, _ = scopes._details(rest)
                    if op_name is not None and "/" in op_name:
                        named += 1
                        assert table["ops"][name] in scopes.NAMES, (
                            kind, name, op_name)
            assert named > 50, kind
            assert set(table["ops"].values()) <= set(scopes.NAMES) | {
                scopes.UNSCOPED}

    def test_experts_and_optimizer_have_operations(self, scope_tables):
        parts = set(scope_tables["experts"][1]["ops"].values())
        for part in ("moe_router", "moe_layout", "moe_experts",
                     "moe_shared", "optimizer", "attn", "mlp", "embed",
                     "head"):
            assert part in parts, part
        dense = set(scope_tables["dense"][1]["ops"].values())
        assert {"attn", "mlp", "embed", "head", "optimizer"} <= dense
        assert not any(p.startswith("moe_") for p in dense)

    def test_the_table_compiles_nothing_more(self, scope_tables):
        for kind, (st, _, compiles, _) in scope_tables.items():
            assert compiles == 1, kind
            assert st._jitted._cache_size() == 1, kind
            assert st.host_counters()["builds"] == 1

    def test_loss_bit_equal_without_the_table(self, scope_tables):
        from paddle_tpu.observability import scopes

        kept = scopes.last_table()
        st, losses = _experts_step(build_table=False)
        assert st._executable is None
        assert scopes.last_table() is kept        # nothing published
        assert losses == scope_tables["experts"][3]
        dense, ids = _tiny_step(False, seed=6)
        dense._compile = lambda args: None
        assert ([float(dense(ids, ids, ids)) for _ in range(2)]
                == scope_tables["dense"][3])

    def test_a_moved_scope_is_compiled_again(self, tmp_path, monkeypatch):
        """The persistent cache's key leaves HLO metadata out unless told
        otherwise: the step's compile takes it in, so a step whose only
        change is where a scope lies compiles again, and its table names
        the parts where they are now, not where the cached program had
        them."""
        from paddle_tpu.core.compile_cache import compile_log, start_compile_log
        from paddle_tpu.observability import scopes

        start_compile_log()

        def build():
            n0 = len(compile_log())
            st, ids = _tiny_step(False, seed=7)
            st(ids, ids, ids)
            (e,) = [e for e in compile_log()[n0:]
                    if e["fun_name"] == "jit(_step_fn)"]
            return e["cache"], set(scopes.last_table()["ops"].values())

        with _temporary_persistent_cache(tmp_path):
            first, parts = build()
            named = scopes.scope
            monkeypatch.setattr(scopes, "scope", lambda name: named(
                "attn" if name == "mlp" else name))
            again, moved = build()
        assert (first, again) == ("miss", "miss")
        assert "mlp" in parts and "mlp" not in moved

    def test_innermost_listed_name_wins(self):
        from paddle_tpu.observability import scopes

        assert scopes.part_of(
            "jit(_step_fn)/transpose(jvp(loss))/mlp/moe_experts/dot") == \
            "moe_experts"
        assert scopes.part_of("jit(_step_fn)/jvp(loss)/attn/mla_rope/mul") \
            == "attn"
        assert scopes.part_of(
            "jit(_step_fn)/transpose(jvp(kda))/checkpoint/x;"
            "jit(_step_fn)/head/y") == "kda"
        assert scopes.part_of("jit(_step_fn)/head_ce/add") == scopes.UNSCOPED
        with pytest.raises(ValueError):
            scopes.scope("final_norm")

    def test_xla_made_instructions_take_a_neighbours_part(self):
        from paddle_tpu.observability import scopes

        text = """HloModule jit__step_fn, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_step_fn)/jvp(loss)/mlp/mul"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte = f32[4]{0} get-tuple-element(%p), index=1
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%gte, %gte)
}

%cond (q: (s32[], f32[4])) -> pred[] {
  %q = (s32[], f32[4]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

ENTRY %main.9 (w.1: f32[4], x.1: (s32[], f32[4])) -> f32[4] {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="param_vals[0]"}
  %x.1 = (s32[], f32[4]{0}) parameter(1)
  %copy-start.1 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]) copy-start(%w.1)
  %copy-done.1 = f32[4]{0:S(1)} copy-done((f32[4]{0:S(1)}, f32[4]{0}, u32[]) %copy-start.1)
  %fusion.3 = f32[4]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation
  %gather.2 = f32[4]{0} copy(%fusion.3), metadata={op_name="gather"}
  %while.4 = (s32[], f32[4]{0}) while(%x.1), condition=%cond, body=%body, metadata={op_name="jit(_step_fn)/optimizer/while"}
  ROOT %add.5 = f32[4]{0} add(%gather.2, %gather.2), metadata={op_name="jit(_step_fn)/add"}
}
"""
        table = scopes.table(text)
        assert table["module"] == "jit__step_fn"
        assert table["ops"] == {
            "copy-start.1": "mlp", "copy-done.1": "mlp",   # by their user
            "fusion.3": "mlp",                             # by its root
            "gather.2": "mlp",                             # by its operand
            "while.4": "optimizer", "add.5": scopes.UNSCOPED,
            "gte": "optimizer", "tuple.2": "optimizer",    # the loop's
            "c": "optimizer"}


# ---------------------------------------------------------------------------
class _HostEngine:
    """test_router's FakeEngine pattern: REAL scheduler + allocator behind
    the transport seam, deterministic tokens — router/replica/scheduler
    span machinery runs for real without per-engine XLA compiles."""

    def __init__(self):
        from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                        PageAllocator)

        self.allocator = PageAllocator(64, 4)
        self.scheduler = ContinuousBatchingScheduler(self.allocator, 4, 64)
        self.decode_retraces_after_warmup = 0

    def submit(self, prompt, max_new_tokens=16, temperature=0.0, top_k=0,
               top_p=1.0, eos_id=None, stream_cb=None):
        from paddle_tpu.serving import Request

        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens),
                      stream_cb=stream_cb)
        return self.scheduler.submit(req)

    def step(self):
        from paddle_tpu.serving import RequestState

        for req in self.scheduler.admissions():
            self.scheduler.activate(req)
        self.scheduler.grow()
        for req in list(self.scheduler.running):
            tok = (int(np.sum(req.prompt)) * 31
                   + 7 * len(req.generated)) % 997
            req.generated.append(tok)
            if req.stream_cb is not None:
                req.stream_cb(req, tok)
            if len(req.generated) >= req.max_new_tokens:
                self.scheduler.finish(req, RequestState.FINISHED)

    def stats(self):
        return {"queue_depth": self.scheduler.queue_depth,
                "oldest_wait_age_s": 0.0, "in_flight": 0, "slot_fill": 0.0,
                "decode_retraces_after_warmup": 0, "free_pages": 10}

    def cancel(self, rid):
        return self.scheduler.cancel(rid)

    def release(self, rid):
        self.scheduler.release(rid)


class TestTracePropagation:
    def test_router_to_engine_trace_ids_two_replicas(self):
        """The acceptance path: trace ids minted at the router correlate
        spans from router -> replica -> scheduler (the engine-side
        admission) across a TWO-replica in-process fleet, and the exported
        Chrome file carries them."""
        from paddle_tpu.serving import InProcessReplica, Router, RouterConfig

        reps = [InProcessReplica(_HostEngine(), replica_id=i)
                for i in range(2)]
        router = Router(reps, RouterConfig(probe_interval_s=0.05,
                                           gap_timeout_s=5.0))
        obs_tracing.start_tracing()
        try:
            for s in range(4):   # sessions spread over both replicas
                toks, term = router.generate(
                    {"prompt_ids": [1 + s, 2, 3], "max_new_tokens": 4,
                     "session": f"s{s}"})
                assert term.get("done"), term
                assert len(toks) == 4
        finally:
            evs = obs_tracing.stop_tracing()
            router.close(close_transports=True)
        by_trace = {}
        replicas_used = set()
        for e in evs:
            args = e.get("args", {})
            t = args.get("trace_id")
            if t:
                by_trace.setdefault(t, set()).add(args.get("component"))
            if e["name"] == "replica.open_stream":
                replicas_used.add(args.get("replica"))
        full = [t for t, comps in by_trace.items()
                if {"router", "replica", "scheduler"} <= comps]
        assert len(full) == 4, by_trace   # every request fully correlated
        assert replicas_used == {0, 1}    # genuinely two replicas


# ---------------------------------------------------------------------------
# real engine: /metrics endpoint, engine spans, zero-retrace guard
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_engine():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return ServingEngine(m, ServingConfig(page_size=4, num_pages=64,
                                          decode_batch=4, prefill_chunk=8,
                                          max_seq_len=64))


class TestRealEngineObservability:
    def test_engine_spans_and_zero_retrace_under_instrumentation(
            self, real_engine):
        """Decode-step metrics collection + tracing + scrapes add NO new
        compilations, and the engine emits prefill/decode spans carrying
        the request trace id."""
        eng = real_engine
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, n).astype(np.int32) for n in (5, 9)]
        eng.generate(prompts, max_new_tokens=4)      # warm every bucket
        eng.mark_warmup()
        reg = obs_metrics.registry()
        obs_tracing.start_tracing()
        try:
            rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            for rid in rids:   # the trace id rides the request object
                eng.scheduler.get(rid).trace_id = f"tr{rid}"
            while not eng.scheduler.idle:
                eng.step()
                reg.prometheus_text()                # scrape mid-decode
            for rid in rids:
                eng.release(rid)
        finally:
            evs = obs_tracing.stop_tracing()
        assert eng.decode_retraces_after_warmup == 0
        prefills = [e for e in evs if e["name"] == "engine.prefill"]
        decodes = [e for e in evs if e["name"] == "engine.decode_step"]
        assert {e["args"]["trace_id"] for e in prefills} == {
            f"tr{r}" for r in rids}
        assert decodes
        traced = set()
        for e in decodes:
            traced.update(e["args"].get("trace_ids", []))
        assert traced == {f"tr{r}" for r in rids}
        # the host's parts of a decode step: one of each a step, in order,
        # inside the step's span and naming it as their parent
        parts = ["engine.decode.pack", "engine.decode.dispatch",
                 "engine.decode.readback", "engine.decode.apply"]
        by_part = [sorted((e["ts"], e["dur"]) for e in evs
                          if e["name"] == p) for p in parts]
        assert all(len(p) == len(decodes) for p in by_part)
        for step, *subs in zip(sorted((e["ts"], e["dur"]) for e in decodes),
                               *by_part):
            ends = [ts + dur for ts, dur in subs]
            assert step[0] <= subs[0][0] and ends[-1] <= step[0] + step[1]
            assert all(end <= nxt[0] for end, nxt in zip(ends, subs[1:]))
        assert {e["args"]["parent"] for e in evs
                if e["name"] in parts} == {"engine.decode_step"}
        waits = [e for e in evs if e["name"] == "engine.submit_wait"]
        assert len(waits) == len(rids)
        assert [e for e in evs if e["name"] == "engine.admit"]
        st = eng.stats()
        assert st["submit_lock_wait_ms_total"] >= st[
            "submit_lock_wait_ms_max"] >= 0
        assert set(st["compile"]) >= {"programs", "secs", "hits", "misses"}

    def test_submit_wait_counts_the_step_lock(self, real_engine):
        """A submitter that finds the step lock held waits, and the wait is
        summed and maxed into stats()."""
        eng = real_engine
        before = eng.stats()
        rids = []
        with eng._step_lock:
            t = threading.Thread(
                target=lambda: rids.append(eng.submit(
                    np.arange(1, 6, dtype=np.int32), max_new_tokens=2)))
            t.start()
            time.sleep(0.05)
        t.join(10)
        assert not t.is_alive()
        while not eng.scheduler.idle:
            eng.step()
        eng.release(rids[0])
        after = eng.stats()
        assert after["submit_lock_wait_ms_max"] >= 40
        assert (after["submit_lock_wait_ms_total"]
                - before["submit_lock_wait_ms_total"]) >= 40

    def test_metrics_endpoint_alongside_healthz_and_stats(self, real_engine):
        eng = real_engine
        srv = eng.serve_http(0, block=False)
        accept = threading.Thread(target=srv.serve_forever, daemon=True)
        accept.start()
        try:
            port = srv.server_port

            def get(path):
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=10)
                conn.request("GET", path)
                resp = conn.getresponse()
                body = resp.read()
                ct = resp.getheader("Content-Type")
                conn.close()
                return resp.status, ct, body

            status, ct, body = get("/metrics")
            assert status == 200
            assert ct.startswith("text/plain; version=0.0.4")
            text = body.decode()
            assert "# TYPE serving_engine_queue_depth gauge" in text
            assert "serving_engine_committed_tokens_total" in text
            # /healthz and /stats stay byte-compatible JSON
            status, ct, body = get("/healthz")
            assert status == 200 and ct == "application/json"
            assert json.loads(body)["ok"] is True
            status, ct, body = get("/stats")
            assert status == 200
            st = json.loads(body)
            assert set(eng.stats()) == set(st)
        finally:
            eng.shutdown_http()

    def test_page_eviction_emits_journal_event(self):
        from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                        PageAllocator, Request)

        alloc = PageAllocator(6, 4)                  # 5 usable pages
        sched = ContinuousBatchingScheduler(alloc, 2, 64)
        before = len(obs_events.journal().recent(component="serving",
                                                 event="page_eviction"))
        r1 = Request(prompt=np.arange(1, 9, dtype=np.int32))   # 2 pages
        r2 = Request(prompt=np.arange(1, 9, dtype=np.int32))
        for r in (r1, r2):
            sched.submit(r)
        for r in sched.admissions():
            sched.activate(r)
        # grow both requests until the pool exhausts -> youngest evicted
        while not any(r.evictions for r in (r1, r2)):
            for r in list(sched.running):
                r.generated.append(1)
            sched.grow()
        recs = obs_events.journal().recent(component="serving",
                                           event="page_eviction")
        assert len(recs) > before
        assert recs[-1]["severity"] == "warn"
