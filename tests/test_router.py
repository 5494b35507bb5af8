"""Fleet-front router (PR 11): rendezvous placement, circuit breaking,
draining, bounded failover, admission/shed degradation, the serving chaos
matrix over every `serving.*` fault point, and the routed-vs-direct
acceptance checks.

Most tests run against `FakeEngine` — the REAL ContinuousBatchingScheduler
+ PageAllocator (admission, QueueFull pushback, eviction re-queues, cancel/
release bookkeeping) around a deterministic token function instead of a
compiled decode program — so router behavior is exercised on the true
scheduling machinery without per-engine XLA compiles. The token function
depends only on (prompt, index), the same property the PR-9
eviction-equivalence contract proves for greedy decoding, so a failover
re-prefill on a peer MUST reproduce the exact stream. One class at the end
routes a real ServingEngine for the zero-decode-retrace + greedy-parity
acceptance criteria.
"""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.serving import (ContinuousBatchingScheduler, PageAllocator,
                                QueueFull, Request)
from paddle_tpu.serving.replica import (InProcessReplica, ReplicaDead,
                                        ReplicaError, StreamCut)
from paddle_tpu.serving.router import (Router, RouterConfig, _Dispatch,
                                       backoff_delays, rendezvous_order)

# serving.* fault points as LITERALS (the registry-coverage lint greps for
# them; the routed chaos matrix below injects each one)
SERVING_POINTS = ["serving.replica.kill", "serving.replica.slow",
                  "serving.dispatch.drop", "serving.stream.cut"]


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------
class FakeEngine:
    """Host-only ServingEngine stand-in behind the transport seam: real
    scheduler + allocator, deterministic tokens, optional per-step delay
    so streams have duration (failure windows exist mid-stream)."""

    def __init__(self, num_pages=64, page_size=4, max_seq_len=64,
                 max_waiting=0, decode_batch=4, step_delay_s=0.0):
        self.decode_batch = decode_batch
        self.allocator = PageAllocator(num_pages, page_size)
        self.scheduler = ContinuousBatchingScheduler(
            self.allocator, decode_batch, max_seq_len,
            max_waiting=max_waiting)
        self.step_delay_s = step_delay_s
        self.steps = 0
        self.decode_retraces_after_warmup = 0

    @staticmethod
    def token(prompt, i):
        """Deterministic greedy stand-in: depends ONLY on (prompt, index),
        so any replica — and any post-eviction/failover re-prefill —
        produces the identical stream."""
        return (int(np.sum(np.asarray(prompt, np.int64))) * 31 + 7 * i) % 997

    def submit(self, prompt, max_new_tokens=16, temperature=0.0, top_k=0,
               top_p=1.0, eos_id=None, stream_cb=None):
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), eos_id=eos_id, stream_cb=stream_cb)
        return self.scheduler.submit(req)

    def step(self):
        if self.step_delay_s:
            time.sleep(self.step_delay_s)
        for req in self.scheduler.admissions():
            self.scheduler.activate(req)
        self.scheduler.grow()
        self.steps += 1
        for req in list(self.scheduler.running):
            tok = self.token(req.prompt, len(req.generated))
            req.generated.append(tok)
            if req.stream_cb is not None:
                req.stream_cb(req, tok)
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.generated) >= req.max_new_tokens):
                self.scheduler.finish(req)
        return bool(self.scheduler.running)

    def cancel(self, rid):
        return self.scheduler.cancel(rid)

    def release(self, rid):
        self.scheduler.release(rid)

    def stats(self):
        running = len(self.scheduler.running)
        return {"queue_depth": self.scheduler.queue_depth,
                "oldest_wait_age_s": self.scheduler.oldest_wait_age(),
                "in_flight": running + self.scheduler.queue_depth,
                "slot_fill": running / max(self.decode_batch, 1),
                "decode_retraces_after_warmup": 0,
                "free_pages": self.allocator.free_pages,
                "waiting_limit": self.scheduler.max_waiting}


def _expected(prompt, n):
    return [FakeEngine.token(prompt, i) for i in range(n)]


class ScriptedStream:
    def __init__(self, events):
        self._events = list(events)
        self.closed = False

    def next_event(self, timeout_s):
        if not self._events:
            time.sleep(min(timeout_s, 0.005))
            return None                      # silence (gap accounting)
        ev = self._events.pop(0)
        if isinstance(ev, Exception):
            raise ev
        if ev is None:
            time.sleep(min(timeout_s, 0.005))
            return None
        return ev

    def close(self):
        self.closed = True


class ScriptedReplica:
    """Pure-transport fake for router unit tests: scripted probe results
    and stream factories, with every payload/handle recorded."""

    def __init__(self, rid, stream_factory=None):
        self.replica_id = rid
        self.probe_result = {"ok": True, "queue_depth": 0, "slot_fill": 0.0}
        self.probe_exc = None
        self.stream_factory = stream_factory
        self.payloads = []
        self.handles = []

    def probe(self):
        if self.probe_exc is not None:
            raise self.probe_exc
        return dict(self.probe_result)

    def open_stream(self, payload):
        self.payloads.append(dict(payload))
        if self.stream_factory is not None:
            h = self.stream_factory(payload)
        else:
            toks = _expected(payload["prompt_ids"],
                             int(payload.get("max_new_tokens", 16)))
            h = ScriptedStream([{"token": t} for t in toks]
                               + [{"done": True}])
        self.handles.append(h)
        return h


def _cfg(**over):
    base = dict(probe_interval_s=0.01, failure_threshold=3,
                breaker_cooldown_s=0.05, dispatch_attempts=3,
                backoff_initial_s=0.005, backoff_max_s=0.02,
                gap_timeout_s=0.3, max_inflight=8, shed_queue_depth=10_000,
                shed_max_new_tokens=2, retry_after_s=0.25)
    base.update(over)
    return RouterConfig(**base)


def _payload(prompt, n=5, **kw):
    return {"prompt_ids": [int(t) for t in np.asarray(prompt).ravel()],
            "max_new_tokens": n, **kw}


# ---------------------------------------------------------------------------
# placement primitives
# ---------------------------------------------------------------------------
class TestRendezvous:
    def test_remap_minimality_on_removal_and_addition(self):
        ids = [0, 1, 2, 3]
        keys = [f"session-{i}" for i in range(200)]
        first = {k: rendezvous_order(k, ids)[0] for k in keys}
        # every replica owns a share (no degenerate hash)
        assert set(first.values()) == set(ids)
        # removing id 2 remaps ONLY the keys that ranked it first
        for k in keys:
            f2 = rendezvous_order(k, [0, 1, 3])[0]
            if first[k] != 2:
                assert f2 == first[k], k
            else:
                assert f2 in (0, 1, 3)
        # adding id 4 steals ONLY the keys that now rank it first
        for k in keys:
            f3 = rendezvous_order(k, ids + [4])[0]
            if f3 != 4:
                assert f3 == first[k], k

    def test_order_is_deterministic_permutation(self):
        ids = [5, 9, 2]
        o1 = rendezvous_order("k", ids)
        assert o1 == rendezvous_order("k", [9, 2, 5])
        assert sorted(o1) == sorted(ids)

    def test_backoff_delays_double_and_cap(self):
        assert backoff_delays(4, 0.05, 0.15) == [0.05, 0.1, 0.15]
        assert backoff_delays(3, 0.1, 10.0) == [0.1, 0.2]
        assert backoff_delays(1, 0.1, 1.0) == []


# ---------------------------------------------------------------------------
# circuit breaker + drain
# ---------------------------------------------------------------------------
class TestBreaker:
    def test_probe_failures_trip_after_threshold(self):
        a, b = ScriptedReplica(0), ScriptedReplica(1)
        r = Router([a, b], _cfg(), start_monitor=False)
        try:
            a.probe_exc = ReplicaError("probe down")
            for i in range(3):
                r.monitor_tick()
                want_open = i >= 2                # threshold = 3
                assert (r.stats()["replicas"]["0"]["circuit"]
                        == ("open" if want_open else "closed"))
            h = r.health()
            assert h["ok"] and h["healthy"] == [1]
        finally:
            r.close()

    def test_half_open_trial_reopens_then_closes(self):
        a, b = ScriptedReplica(0), ScriptedReplica(1)
        r = Router([a, b], _cfg(breaker_cooldown_s=0.03),
                   start_monitor=False)
        try:
            a.probe_exc = ReplicaError("down")
            for _ in range(3):
                r.monitor_tick()
            assert r.stats()["replicas"]["0"]["trips"] == 1
            r.monitor_tick()          # still cooling: no trial, still open
            assert r.stats()["replicas"]["0"]["circuit"] == "open"
            time.sleep(0.04)
            r.monitor_tick()          # half-open trial fails -> re-open
            s = r.stats()["replicas"]["0"]
            assert s["circuit"] == "open" and s["trips"] == 2
            assert "half-open" in s["last_cause"]
            time.sleep(0.04)
            a.probe_exc = None
            r.monitor_tick()          # trial succeeds -> closed
            s = r.stats()["replicas"]["0"]
            assert s["circuit"] == "closed"
            assert s["consecutive_failures"] == 0
        finally:
            r.close()

    def test_dispatch_failures_count_toward_breaker(self):
        def boom(payload):
            raise ReplicaError("dispatch refused")

        a = ScriptedReplica(0, stream_factory=boom)
        b = ScriptedReplica(1)
        r = Router([a, b], _cfg(failure_threshold=2), start_monitor=False)
        try:
            p = np.arange(1, 5)
            for _ in range(2):        # ties go to the lowest rid -> a first
                toks, term = r.generate(_payload(p))
                assert term["done"] and term["failovers"] == 1
                assert toks == _expected(p, 5)
            s = r.stats()["replicas"]["0"]
            assert s["circuit"] == "open" and s["trips"] == 1
            # an OPEN circuit is skipped entirely: no third strike, no retry
            toks, term = r.generate(_payload(p))
            assert term["done"] and term["failovers"] == 0
            assert term["replica"] == 1
            assert len(a.payloads) == 2
        finally:
            r.close()

    def test_trip_drains_inflight_oldest_first(self):
        a, b = ScriptedReplica(0), ScriptedReplica(1)
        r = Router([a, b], _cfg(), start_monitor=False)
        try:
            # white-box: synthesize in-flight dispatches bound to each slot
            ctxs = {}
            for seq, (rid, at) in enumerate([(0, 3.0), (0, 1.0), (1, 0.5),
                                             (0, 2.0)]):
                c = _Dispatch(seq=seq, arrival_t=at, abort=threading.Event())
                c.replica_id = rid
                r._inflight[seq] = c
                ctxs[seq] = c
            seqs = r.drain(0, why="maintenance")
            assert seqs == [1, 3, 0]   # replica-0 dispatches, arrival order
            assert all(ctxs[s].abort.is_set() for s in seqs)
            assert ctxs[2].abort.is_set() is False     # replica 1 untouched
            assert all(ctxs[s].abort_why == "maintenance" for s in seqs)
            assert r.stats()["replicas"]["0"]["draining"] is True
            assert r.stats()["drained"] == 3
            # draining replicas take no new placements until undrain
            assert r._pick(None, ()).rid == 1
            r.undrain(0)
            assert r._pick(None, ()).rid in (0, 1)
        finally:
            r._inflight.clear()
            r.close()


# ---------------------------------------------------------------------------
# placement, admission, degradation
# ---------------------------------------------------------------------------
class TestPlacement:
    def test_session_affinity_and_minimal_remap_on_trip(self):
        reps = [ScriptedReplica(i) for i in range(3)]
        r = Router(reps, _cfg(), start_monitor=False)
        try:
            key = "user-42"
            home = rendezvous_order(key, [0, 1, 2])[0]
            p = np.arange(1, 6)
            for _ in range(3):        # sticky across calls
                toks, term = r.generate(_payload(p, session=key))
                assert term["replica"] == home
            reps[home].probe_exc = ReplicaError("down")
            for _ in range(3):
                r.monitor_tick()
            alive = [i for i in range(3) if i != home]
            toks, term = r.generate(_payload(p, session=key))
            assert term["replica"] == rendezvous_order(key, alive)[0]
            # an unkeyed session elsewhere is unaffected by the remap
            assert toks == _expected(p, 5)
        finally:
            r.close()

    def test_unkeyed_goes_to_least_loaded(self):
        a, b = ScriptedReplica(0), ScriptedReplica(1)
        a.probe_result = {"ok": True, "queue_depth": 7, "slot_fill": 1.0}
        r = Router([a, b], _cfg(), start_monitor=False)
        try:
            r.monitor_tick()          # load the probe views
            toks, term = r.generate(_payload(np.arange(1, 4)))
            assert term["replica"] == 1
        finally:
            r.close()

    def test_admission_refuses_past_max_inflight(self):
        a = ScriptedReplica(0)
        r = Router([a], _cfg(max_inflight=2), start_monitor=False)
        try:
            for seq in (91, 92):      # white-box: saturate the in-flight cap
                c = _Dispatch(seq=seq, arrival_t=0.0,
                              abort=threading.Event())
                r._inflight[seq] = c
            rej = r.admission_check({"prompt_ids": [1]})
            assert rej["status"] == 503
            assert rej["retry_after"] == pytest.approx(0.25)
            toks, term = r.generate(_payload(np.arange(1, 3)))
            assert toks == [] and term["error"] == "refused"
            assert term["retry_after"] == pytest.approx(0.25)
            assert r.stats()["refused"] == 2
            r._inflight.clear()
            assert r.admission_check({"prompt_ids": [1]}) is None
        finally:
            r._inflight.clear()
            r.close()

    def test_admission_refuses_with_no_healthy_replica(self):
        a = ScriptedReplica(0)
        r = Router([a], _cfg(), start_monitor=False)
        try:
            a.probe_exc = ReplicaError("down")
            for _ in range(3):
                r.monitor_tick()
            rej = r.admission_check({"prompt_ids": [1]})
            assert rej["status"] == 503 and "healthy" in rej["message"]
            assert r.health()["ok"] is False
        finally:
            r.close()

    def test_shed_caps_max_new_tokens_before_dropping(self):
        a = ScriptedReplica(0)
        r = Router([a], _cfg(shed_queue_depth=0, shed_max_new_tokens=2),
                   start_monitor=False)
        try:
            p = np.arange(1, 7)
            toks, term = r.generate(_payload(p, n=10))
            assert term["done"] and term.get("shed") is True
            assert a.payloads[0]["max_new_tokens"] == 2
            assert toks == _expected(p, 2)     # degraded, not dropped
            assert r.stats()["sheds"] == 1
            # under the watermark no shed: raise it and re-check
            r.cfg.shed_queue_depth = 10_000
            toks, term = r.generate(_payload(p, n=4))
            assert "shed" not in term and toks == _expected(p, 4)
        finally:
            r.close()

    def test_queue_full_excludes_without_breaker_strike(self):
        def full(payload):
            raise QueueFull(5, 5)

        a = ScriptedReplica(0, stream_factory=full)
        b = ScriptedReplica(1)
        r = Router([a, b], _cfg(), start_monitor=False)
        try:
            p = np.arange(2, 6)
            toks, term = r.generate(_payload(p))
            assert term["done"] and term["replica"] == 1
            assert term["failovers"] == 1
            assert toks == _expected(p, 5)
            s = r.stats()["replicas"]["0"]
            assert s["circuit"] == "closed"
            assert s["consecutive_failures"] == 0     # pushback != illness
        finally:
            r.close()

    def test_all_replicas_queue_full_maps_to_503_retry_after(self):
        def full(payload):
            raise QueueFull(5, 5)

        reps = [ScriptedReplica(i, stream_factory=full) for i in range(2)]
        r = Router(reps, _cfg(dispatch_attempts=2), start_monitor=False)
        try:
            toks, term = r.generate(_payload(np.arange(1, 4)))
            assert toks == [] and term["error"] == "queue_full"
            assert term["retry_after"] == pytest.approx(0.25)
            assert all(r.stats()["replicas"][str(i)]["circuit"] == "closed"
                       for i in range(2))
        finally:
            r.close()


# ---------------------------------------------------------------------------
# failover relay
# ---------------------------------------------------------------------------
class TestFailover:
    def test_mid_stream_cut_resumes_without_double_emit(self):
        p = np.arange(3, 9)
        want = _expected(p, 6)

        def cut_after_2(payload):
            toks = _expected(payload["prompt_ids"],
                             int(payload["max_new_tokens"]))
            return ScriptedStream([{"token": toks[0]}, {"token": toks[1]},
                                   StreamCut("connection died")])

        a = ScriptedReplica(0, stream_factory=cut_after_2)
        b = ScriptedReplica(1)
        r = Router([a, b], _cfg(), start_monitor=False)
        try:
            toks, term = r.generate(_payload(p, n=6))
            assert toks == want                    # each token EXACTLY once
            assert term["done"] and term["failovers"] == 1
            assert term["replica"] == 1
            assert a.handles[0].closed             # no leaked stream handle
            # the peer replayed from its own prefill: it was handed the
            # ORIGINAL prompt, not a resume cursor
            assert b.payloads[0]["prompt_ids"] == [int(t) for t in p]
            assert r._inflight == {}               # no per-request residue
        finally:
            r.close()

    def test_wedged_stream_fails_over_after_gap_timeout(self):
        a = ScriptedReplica(0, stream_factory=lambda p: ScriptedStream([]))
        b = ScriptedReplica(1)
        r = Router([a, b], _cfg(gap_timeout_s=0.1), start_monitor=False)
        try:
            p = np.arange(1, 5)
            t0 = time.monotonic()
            toks, term = r.generate(_payload(p))
            assert time.monotonic() - t0 >= 0.1    # silence cost the gap
            assert toks == _expected(p, 5)
            assert term["failovers"] == 1
            assert r.stats()["replicas"]["0"]["consecutive_failures"] == 1
        finally:
            r.close()

    def test_exhausted_attempts_yield_one_typed_error(self):
        def boom(payload):
            raise ReplicaError("always down")

        reps = [ScriptedReplica(i, stream_factory=boom) for i in range(4)]
        r = Router(reps, _cfg(dispatch_attempts=3, failure_threshold=99),
                   start_monitor=False)
        try:
            events = list(r.stream(_payload(np.arange(1, 4))))
            assert len(events) == 1                # exactly ONE terminal
            assert events[0]["error"] == "failover_exhausted"
            assert events[0]["failovers"] == 2
            assert r.stats()["failed"] == 1
        finally:
            r.close()

    def test_every_circuit_open_yields_typed_error(self):
        def boom(payload):
            raise ReplicaError("down")

        reps = [ScriptedReplica(i, stream_factory=boom) for i in range(2)]
        r = Router(reps, _cfg(dispatch_attempts=5), start_monitor=False)
        try:
            events = list(r.stream(_payload(np.arange(1, 4))))
            assert len(events) == 1
            assert events[0]["error"] == "no_healthy_replica"
            assert events[0]["retry_after"] == pytest.approx(0.25)
        finally:
            r.close()

    def test_deadline_yields_single_timeout_event(self):
        a = ScriptedReplica(0, stream_factory=lambda p: ScriptedStream([]))
        r = Router([a], _cfg(gap_timeout_s=5.0), start_monitor=False)
        try:
            t0 = time.monotonic()
            events = list(r.stream(_payload(np.arange(1, 4)),
                                   deadline=time.monotonic() + 0.08))
            assert [e.get("error") for e in events] == ["timeout"]
            assert 0.05 < time.monotonic() - t0 < 2.0
            assert a.handles[0].closed
        finally:
            r.close()

    def test_dispatch_drop_point_detected_within_gap_timeout(self):
        reps = [ScriptedReplica(i) for i in range(2)]
        r = Router(reps, _cfg(gap_timeout_s=0.08), start_monitor=False)
        try:
            faults.arm("serving.dispatch.drop")
            p = np.arange(4, 9)
            t0 = time.monotonic()
            toks, term = r.generate(_payload(p))
            assert time.monotonic() - t0 >= 0.08
            assert toks == _expected(p, 5)
            assert term["done"] and term["failovers"] == 1
            assert faults.fired("serving.dispatch.drop") == 1
        finally:
            faults.reset()
            r.close()


# ---------------------------------------------------------------------------
# the in-process replica transport (FakeEngine-backed)
# ---------------------------------------------------------------------------
class TestInProcessReplica:
    def test_probe_readiness_fields_and_stream_roundtrip(self):
        rep = InProcessReplica(FakeEngine(), replica_id=3)
        try:
            pr = rep.probe()
            for k in ("queue_depth", "oldest_wait_age_s", "slot_fill",
                      "decode_retraces_after_warmup", "free_pages"):
                assert k in pr, k
            assert pr["ok"] is True and pr["replica"] == 3
            p = np.arange(1, 6)
            h = rep.open_stream(_payload(p, n=4))
            toks, done = [], None
            while done is None:
                ev = h.next_event(1.0)
                if ev is None:
                    continue
                if "token" in ev:
                    toks.append(ev["token"])
                else:
                    done = ev
            h.close()
            assert toks == _expected(p, 4) and done["done"]
            # close released the engine-side bookkeeping
            assert rep.engine.scheduler._by_rid == {}
            assert rep.engine.allocator.used_pages == 0
        finally:
            rep.close()

    def test_kill_point_fails_probes_and_streams_fast(self):
        eng = FakeEngine()
        rep = InProcessReplica(eng, replica_id=0)
        try:
            faults.arm("serving.replica.kill")
            deadline = time.time() + 3.0
            while rep.dead_cause is None and time.time() < deadline:
                time.sleep(0.005)
            assert rep.dead_cause is not None
            with pytest.raises(ReplicaDead):
                rep.probe()
            with pytest.raises(ReplicaDead):
                rep.open_stream(_payload(np.arange(1, 3)))
            assert faults.fired("serving.replica.kill") == 1
        finally:
            faults.reset()
            rep.close()        # joins the (already-exited) driver thread

    def test_slow_point_degrades_without_killing(self):
        eng = FakeEngine()
        rep = InProcessReplica(eng, replica_id=0, slow_stall_s=0.05)
        try:
            faults.arm("serving.replica.slow")
            p = np.arange(2, 7)
            h = rep.open_stream(_payload(p, n=3))
            toks = []
            deadline = time.time() + 5.0
            while len(toks) < 3 and time.time() < deadline:
                ev = h.next_event(0.2)
                if ev and "token" in ev:
                    toks.append(ev["token"])
                elif ev and ev.get("done"):
                    break
            h.close()
            assert toks == _expected(p, 3)         # stalled, never wrong
            assert rep.dead_cause is None
            assert faults.fired("serving.replica.slow") == 1
        finally:
            faults.reset()
            rep.close()

    def test_stream_cut_point_raises_at_transport_seam(self):
        rep = InProcessReplica(FakeEngine(), replica_id=0)
        try:
            h = rep.open_stream(_payload(np.arange(1, 4), n=2))
            faults.arm("serving.stream.cut")
            with pytest.raises(StreamCut):
                for _ in range(50):
                    h.next_event(0.05)
            assert h._closed                      # cut also cleaned up
            assert faults.fired("serving.stream.cut") == 1
        finally:
            faults.reset()
            rep.close()


# ---------------------------------------------------------------------------
# routed fleet: chaos matrix + kill-mid-run + heartbeats
# ---------------------------------------------------------------------------
def _fleet(n=3, step_delay_s=0.002, **cfg_over):
    engines = [FakeEngine(step_delay_s=step_delay_s) for _ in range(n)]
    reps = [InProcessReplica(e, replica_id=i)
            for i, e in enumerate(engines)]
    cfg = _cfg(probe_interval_s=0.03, failure_threshold=2,
               breaker_cooldown_s=0.25, dispatch_attempts=4,
               gap_timeout_s=0.5, max_inflight=64, **cfg_over)
    return engines, reps, Router(reps, cfg)


def _run_clients(router, prompts, n_new, spread_s=0.2):
    """Poisson-ish routed load: one client thread per request, arrivals
    spread over `spread_s`. Returns [(tokens, terminal)] in request order."""
    rng = np.random.RandomState(7)
    offsets = np.sort(rng.uniform(0.0, spread_s, len(prompts)))
    results = [None] * len(prompts)

    def client(i):
        time.sleep(float(offsets[i]))
        results[i] = router.generate(_payload(prompts[i], n=n_new))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert time.time() - t0 < 30.0, "routed run hung"
    return results


class TestRoutedChaosMatrix:
    @pytest.mark.parametrize("point", SERVING_POINTS)
    def test_point_recovers_to_fault_free_streams(self, point):
        """The serving chaos matrix: every registered serving.* point
        injected once during a routed Poisson run — zero lost requests,
        and every stream equals the fault-free (deterministic) stream."""
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 500, int(n)).astype(np.int32)
                   for n in rng.randint(3, 11, 10)]
        n_new = 6
        engines, reps, router = _fleet()
        try:
            # nth: let the fleet serve a beat first, then fire mid-run.
            # dispatch.drop is hit once PER DISPATCH (~10 hits total);
            # the driver-loop/stream-poll points hit every few ms
            nth = 5 if point == "serving.dispatch.drop" else 40
            faults.arm(point, mode="nth", nth=nth)
            results = _run_clients(router, prompts, n_new)
            assert faults.fired(point) == 1, point
            for i, (toks, term) in enumerate(results):
                assert term is not None, f"request {i} got no terminal"
                assert term.get("done") is True, (point, i, term)
                assert toks == _expected(prompts[i], n_new), (point, i)
        finally:
            faults.reset()
            router.close()
            for rep in reps:
                rep.close()
        # zero per-request residue anywhere after the run
        assert router._inflight == {}
        for eng, rep in zip(engines, reps):
            if rep.dead_cause is None:       # a killed replica keeps its
                eng.allocator.check_consistency()   # corpse state by design
                assert eng.allocator.used_pages == 0
                assert eng.scheduler._by_rid == {}

    def test_kill_one_of_three_mid_run_loses_nothing(self):
        """The acceptance scenario: 1 of 3 replicas killed while streams
        are in flight — every accepted request still completes with the
        exact stream, via failover re-prefill on a peer."""
        rng = np.random.RandomState(23)
        prompts = [rng.randint(1, 500, int(n)).astype(np.int32)
                   for n in rng.randint(3, 11, 9)]
        n_new = 24
        engines, reps, router = _fleet(step_delay_s=0.004)
        killed = False
        try:
            def killer():
                # wait until the victim is actually serving, then kill it
                deadline = time.time() + 5.0
                while time.time() < deadline:
                    if len(engines[1].scheduler.running) > 0:
                        break
                    time.sleep(0.002)
                reps[1].kill()

            kt = threading.Thread(target=killer)
            kt.start()
            results = _run_clients(router, prompts, n_new, spread_s=0.1)
            kt.join(timeout=5.0)
            killed = reps[1].dead_cause is not None
            for i, (toks, term) in enumerate(results):
                assert term is not None and term.get("done") is True, (i, term)
                assert toks == _expected(prompts[i], n_new), i
            assert killed
            # in-flight work on the corpse failed over rather than timing out
            assert router.failovers >= 1
            assert router.stats()["replicas"]["1"]["circuit"] == "open"
        finally:
            faults.reset()
            router.close()
            for rep in reps:
                rep.close()
        assert router._inflight == {}
        for i in (0, 2):
            engines[i].allocator.check_consistency()
            assert engines[i].allocator.used_pages == 0
            assert engines[i].scheduler._by_rid == {}

    def test_heartbeat_corpse_trips_breaker_by_name(self):
        """PR-10 liveness behind the router: a killed replica's heartbeat
        goes stale (no clean-exit tombstone) and the monitor trips its
        breaker from dead_peers() — the SAME machinery training uses."""
        store = TCPStore(is_master=True)
        engines = [FakeEngine(), FakeEngine()]
        reps = [InProcessReplica(e, replica_id=i, store=store,
                                 heartbeat_interval_s=0.02)
                for i, e in enumerate(engines)]
        # failure_threshold high: the probe path must NOT be what trips —
        # only the heartbeat verdict may open the circuit
        r = Router(reps, _cfg(failure_threshold=99), store=store,
                   dead_timeout_s=0.12, start_monitor=False)
        try:
            r.monitor_tick()                  # primes the beat watch
            time.sleep(0.05)
            r.monitor_tick()
            assert r.stats()["replicas"]["1"]["circuit"] == "closed"
            reps[1].kill()
            cause = None
            for _ in range(60):
                time.sleep(0.05)
                r.monitor_tick()
                s = r.stats()["replicas"]["1"]
                if s["circuit"] == "open":
                    cause = s["last_cause"]
                    break
            assert cause is not None and "heartbeat stale" in cause
            assert r.stats()["replicas"]["0"]["circuit"] == "closed"
        finally:
            r.close()
            for rep in reps:
                rep.close()
            store.close()


# ---------------------------------------------------------------------------
# HTTP front door (serve.py chassis, FakeEngine replicas)
# ---------------------------------------------------------------------------
class TestHttpFrontend:
    def _serve(self, router):
        srv = router.serve_http(0)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        return srv, srv.server_address[1], t

    def _get(self, port, path):
        import http.client
        import json as json_mod

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = json_mod.loads(resp.read().decode())
        conn.close()
        return resp.status, body

    def _post(self, port, payload):
        import http.client
        import json as json_mod

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        body = json_mod.dumps(payload).encode()
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json",
                      "Content-Length": str(len(body))})
        resp = conn.getresponse()
        events = [json_mod.loads(l) for l in
                  resp.read().decode().splitlines() if l.strip()]
        headers = dict(resp.getheaders())
        conn.close()
        return resp.status, events, headers

    def test_generate_healthz_stats_roundtrip(self):
        engines, reps, router = _fleet(n=2, step_delay_s=0.0)
        srv = None
        try:
            srv, port, _ = self._serve(router)
            status, body = self._get(port, "/healthz")
            assert status == 200 and body["ok"] is True
            assert sorted(body["healthy"]) == [0, 1]
            p = np.arange(5, 11)
            status, events, _ = self._post(port, _payload(p, n=4))
            assert status == 200
            toks = [e["token"] for e in events if "token" in e]
            assert toks == _expected(p, 4)
            assert events[-1]["done"] is True
            status, body = self._get(port, "/stats")
            assert status == 200
            assert body["completed"] == 1 and body["accepted"] == 1
            assert body["replicas"]["0"]["circuit"] == "closed"
        finally:
            if srv is not None:
                srv.shutdown()
            router.close()
            for rep in reps:
                rep.close()

    def test_admission_refusal_is_pre_headers_503_with_retry_after(self):
        engines, reps, router = _fleet(n=2, step_delay_s=0.0)
        srv = None
        try:
            for rep in reps:          # kill the whole fleet
                rep.kill()
            for _ in range(2):        # threshold=2 -> both circuits open
                router.monitor_tick()
            srv, port, _ = self._serve(router)
            status, body = self._get(port, "/healthz")
            assert status == 503 and body["ok"] is False
            status, events, headers = self._post(
                port, _payload(np.arange(1, 4)))
            assert status == 503      # refused BEFORE the ndjson stream
            assert "Retry-After" in headers
            assert "error" in events[0]
        finally:
            if srv is not None:
                srv.shutdown()
            router.close()
            for rep in reps:
                rep.close()


# ---------------------------------------------------------------------------
# real engine behind the router: the acceptance criteria
# ---------------------------------------------------------------------------
class TestRoutedRealEngine:
    @pytest.fixture(scope="class")
    def real(self):
        from test_serving import _engine, _model, _prompts

        m, cfg = _model()
        eng = _engine(m)
        rng = np.random.RandomState(0)
        # compile every decode/prefill bucket OUTSIDE the routed run
        eng.generate(_prompts(rng, cfg, (6, 13, 30)), max_new_tokens=4)
        eng.mark_warmup()
        return m, cfg, eng

    def test_routed_parity_zero_retrace_and_clean_release(self, real):
        from test_serving import _prompts, _teacher_greedy

        m, cfg, eng = real
        rep = InProcessReplica(eng, replica_id=0)
        router = Router([rep], _cfg(gap_timeout_s=10.0))
        try:
            rng = np.random.RandomState(9)
            prompts = _prompts(rng, cfg, (5, 11, 8))
            for p in prompts:
                toks, term = router.generate(_payload(p, n=6))
                assert term["done"] and term["failovers"] == 0
                assert toks == _teacher_greedy(m, p, 6)
            # the PR-9 zero-retrace contract must hold BEHIND the router
            assert eng.decode_retraces_after_warmup == 0
            # engine stats feed the probe path end to end
            pr = rep.probe()
            assert pr["decode_retraces_after_warmup"] == 0
            assert pr["slot_fill"] == 0.0
        finally:
            router.close()
            rep.close()
        # no per-request state retained once streams closed
        assert eng.scheduler._by_rid == {}
        assert eng.allocator.used_pages == 0
        assert router._inflight == {}
