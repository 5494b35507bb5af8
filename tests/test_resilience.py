"""Self-healing training (ISSUE 10): unified fault injection, in-program
anomaly detection, checkpoint rollback, and the supervised recovery loop.

Covers: the fault registry (trigger modes, flag spec, legacy ckpt-flag
alias, counters), the AnomalyDetector (non-finite + median/MAD spike
classification, policies), CompiledTrainStep health checking (bit-identical
healthy trajectories, in-program update skip, poison detection),
run_resilient end-to-end recovery for every fault class (rollback /
feeder crash / killed save / simulated hang — final losses bit-exact vs the
fault-free run), persistent-fault halt with quarantine + budget, the
Model.fit(auto_checkpoint=, resilience=) chaos matrix over EVERY registered
fault point, and the satellites: GradScaler consecutive-skip halt, watchdog
thread-stack dumps, feeder crash context, store barrier retry + rank
heartbeats, the except-pass lint, and registry coverage."""
import json
import math
import os
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed.store  # noqa: F401  (registers store.barrier)
import paddle_tpu.nn as nn
from paddle_tpu.core.flags import set_flags
from paddle_tpu.distributed.checkpoint import elastic
from paddle_tpu.distributed.mesh import build_mesh, set_mesh
from paddle_tpu.distributed.resilience import (AnomalyDetector, IncidentLog,
                                               ResilienceHalt,
                                               ResiliencePolicy, faults,
                                               run_resilient)
from paddle_tpu.io.device_feed import DeviceFeeder, FeederWorkerError
from paddle_tpu.parallel import CompiledTrainStep

# every registered injection point, as LITERALS (the coverage test greps for
# them; test_chaos_matrix_covers_registry pins this list to the registry so
# a new point cannot land without a chaos test)
CHAOS_POINTS = [
    "ckpt.after_commit", "ckpt.after_metadata", "ckpt.after_shard_write",
    "ckpt.after_snapshot", "ckpt.before_commit", "ckpt.before_rename",
    "feeder.collate", "feeder.device_put", "step.grads", "store.barrier",
    "watchdog.hang",
]
# the serving half of the registry (PR 11/12): registered at import of
# paddle_tpu.serving.replica/router/engine, exercised by the routed chaos
# matrix in test_router.py (transport points), the speculative-decode
# degradation test in test_serving.py (serving.spec.verify_mismatch), and
# the host-tier degradation tests in test_kv_hierarchy.py
# (serving.kv.promote_fail), and the disaggregated prefill/decode
# exactly-once tests in test_disagg.py (serving.prefill.kill,
# serving.handoff.drop) — these points fire on serving traffic, so
# injecting them into a Model.fit run would test nothing
SERVING_CHAOS_POINTS = [
    "serving.dispatch.drop", "serving.handoff.drop",
    "serving.kv.promote_fail", "serving.lora.swap_fail",
    "serving.prefill.kill", "serving.replica.kill",
    "serving.replica.slow", "serving.spec.verify_mismatch",
    "serving.stream.cut",
]


@pytest.fixture(autouse=True)
def _teardown():
    yield
    set_mesh(None)


# -- shared tiny problem ------------------------------------------------------
IN_DIM, N_CLS = 8, 3


def _mlp_data(i, batch=8):
    rng = np.random.RandomState(5000 + i)
    x = rng.randn(batch, IN_DIM).astype(np.float32)
    y = rng.randint(0, N_CLS, (batch,)).astype(np.int64)
    return x, y


def _make_step_factory(n_total):
    """(make_step, make_data) for run_resilient over a small float-input
    MLP — float batches, so the step.grads point poisons a LEAF (NaN grads,
    same-step detection)."""

    def make_data(start):
        def gen():
            for i in range(start, n_total):
                yield _mlp_data(i)
        return gen()

    def make_step(det, arrays=None, meta=None):
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(IN_DIM, 16), nn.ReLU(),
                            nn.Linear(16, N_CLS))
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=net.parameters())
        if arrays is not None:
            elastic.restore(arrays, meta, net, opt)
        crit = nn.CrossEntropyLoss()
        step = CompiledTrainStep(net, lambda o, l: crit(o, l), opt,
                                 anomaly_detector=det, metrics_every=0)
        if arrays is not None:
            step.load_resume_extras(arrays, meta)
        return step

    return make_step, make_data


class TestFaultRegistry:
    def test_points_register_at_import(self):
        import paddle_tpu.serving.disagg  # noqa: F401 — serving.* points
        import paddle_tpu.serving.replica  # noqa: F401
        import paddle_tpu.serving.router  # noqa: F401
        assert (set(CHAOS_POINTS) | set(SERVING_CHAOS_POINTS)
                <= set(faults.registered()))
        docs = faults.describe()
        for p in CHAOS_POINTS + SERVING_CHAOS_POINTS:
            assert docs[p], f"{p} has no catalog doc"

    def test_unknown_point_raises(self):
        with pytest.raises(KeyError, match="no.such.point"):
            faults.point("no.such.point")
        with pytest.raises(KeyError, match="registered"):
            faults.arm("no.such.point")

    def test_one_shot(self):
        faults.reset()
        faults.arm("feeder.collate")
        with pytest.raises(faults.FaultInjected) as ei:
            faults.point("feeder.collate")
        assert ei.value.point == "feeder.collate"
        faults.point("feeder.collate")  # spent: quiet
        assert faults.hits("feeder.collate") == 2
        assert faults.fired("feeder.collate") == 1

    def test_nth_hit(self):
        faults.reset()
        faults.arm("feeder.device_put", mode="nth", nth=3)
        faults.point("feeder.device_put")
        faults.point("feeder.device_put")
        with pytest.raises(faults.FaultInjected):
            faults.point("feeder.device_put")
        faults.point("feeder.device_put")  # spent

    def test_probabilistic_deterministic_seed(self):
        faults.reset()
        faults.arm("step.grads", mode="prob", p=0.5, seed=123)
        a = [faults.fire_check("step.grads") for _ in range(32)]
        faults.reset()
        faults.arm("step.grads", mode="prob", p=0.5, seed=123)
        b = [faults.fire_check("step.grads") for _ in range(32)]
        assert a == b and any(a) and not all(a)

    def test_always_until_disarm(self):
        faults.reset()
        faults.arm("store.barrier", mode="always")
        for _ in range(3):
            with pytest.raises(faults.FaultInjected):
                faults.point("store.barrier")
        faults.disarm("store.barrier")
        faults.point("store.barrier")

    def test_flag_spec_arming(self):
        faults.reset()
        set_flags({"fault_injection": "feeder.collate:nth=2"})
        faults.point("feeder.collate")
        with pytest.raises(faults.FaultInjected):
            faults.point("feeder.collate")
        faults.point("feeder.collate")  # spent while flag unchanged
        # editing the flag re-arms from scratch
        set_flags({"fault_injection": "feeder.collate"})
        with pytest.raises(faults.FaultInjected):
            faults.point("feeder.collate")
        set_flags({"fault_injection": ""})

    def test_bad_flag_spec_raises(self):
        faults.reset()
        set_flags({"fault_injection": "feeder.collate:bogus=1"})
        with pytest.raises(ValueError, match="bogus"):
            faults.point("feeder.collate")
        # a typo'd mode must fail loudly, not silently never fire
        set_flags({"fault_injection": "feeder.collate:mode=alwys"})
        with pytest.raises(ValueError, match="alwys"):
            faults.point("feeder.collate")
        set_flags({"fault_injection": "feeder.collate:mode=prob"})
        with pytest.raises(ValueError, match="p>0"):
            faults.point("feeder.collate")
        set_flags({"fault_injection": ""})

    def test_malformed_flag_spec_fails_at_config_time(self):
        """check_flag_spec parses the flag NOW: a typo'd spec must fail at
        supervisor/fit startup, not surface at the first injection site hit
        (which may be the feeder worker thread, where the ValueError would
        be wrapped as FeederWorkerError and misdiagnosed — and retried —
        as an input-pipeline fault)."""
        faults.reset()
        try:
            set_flags({"fault_injection": "feeder.collate:nht=3"})
            with pytest.raises(ValueError, match="nht"):
                faults.check_flag_spec()
            # a typo'd POINT NAME is as silent-deadly as a typo'd option:
            # the chaos run would pass cleanly while testing nothing
            set_flags({"fault_injection": "fedeer.collate:nth=1"})
            with pytest.raises(KeyError, match="fedeer"):
                faults.check_flag_spec()
        finally:
            set_flags({"fault_injection": ""})
        faults.check_flag_spec()  # a clean spec parses quietly

    def test_legacy_ckpt_flag_still_arms(self, tmp_path):
        """The PR-8 kill-point contract survives the migration: the old
        string flag arms ckpt.<point> in always mode and raises
        CheckpointFaultInjected through a REAL save."""
        faults.reset()
        set_flags({"ckpt_fault_injection": "before_rename"})
        with pytest.raises(elastic.CheckpointFaultInjected,
                           match="before_rename"):
            elastic._maybe_inject("before_rename")
        with pytest.raises(elastic.CheckpointFaultInjected):
            elastic._maybe_inject("before_rename")  # always, not one-shot
        set_flags({"ckpt_fault_injection": ""})
        elastic._maybe_inject("before_rename")
        # and CheckpointFaultInjected IS a registry FaultInjected
        assert issubclass(elastic.CheckpointFaultInjected,
                          faults.FaultInjected)

    def test_new_flag_drives_ckpt_points_through_real_save(self, tmp_path):
        paddle.seed(0)
        net = nn.Linear(4, 2)
        snap = elastic.capture_model(net)
        set_flags({"fault_injection": "ckpt.before_rename"})
        with elastic.CheckpointManager(str(tmp_path)) as mgr:
            with pytest.raises(elastic.CheckpointFaultInjected,
                               match="ckpt.before_rename"):
                mgr.save(snap)
            set_flags({"fault_injection": ""})
            assert mgr.latest() is None  # nothing published
            mgr.save(elastic.capture_model(net))
            assert mgr.latest() is not None


class TestAnomalyDetector:
    def test_nonfinite_and_health_flag(self):
        det = AnomalyDetector(policy="rollback", min_history=4)
        assert det.observe(1, 1.0, 0.0) is None
        a = det.observe(2, 0.9, 1.0)  # finite loss but health says bad
        assert a.kind == "nonfinite" and det.pending is a
        det.clear_pending()
        a2 = det.observe(3, float("nan"), 0.0)
        assert a2.kind == "nonfinite"

    def test_spike_median_mad(self):
        det = AnomalyDetector(policy="rollback", min_history=6, mad_k=8.0)
        for i, l in enumerate([2.0, 1.9, 1.95, 1.85, 1.9, 1.8]):
            assert det.observe(i, l, 0.0) is None
        a = det.observe(7, 40.0, 0.0)
        assert a is not None and a.kind == "loss_spike"
        assert a.detail["threshold"] < 40.0

    def test_downward_drift_is_not_a_spike(self):
        det = AnomalyDetector(policy="rollback", min_history=6, mad_k=8.0)
        loss = 5.0
        for i in range(40):  # a healthy decreasing curve with noise
            loss = loss * 0.97 + 0.01 * math.sin(i)
            assert det.observe(i, loss, 0.0) is None, (i, loss)
        assert det.incidents == []

    def test_gate_adapts_to_permanent_level_shift(self):
        """Flagged losses still enter the rolling window: a genuine level
        shift (lr change, curriculum switch) migrates the median so the
        gate adapts — instead of flagging every subsequent step forever
        against a frozen pre-shift window."""
        det = AnomalyDetector(policy="rollback", window=16, min_history=8,
                              mad_k=8.0)
        for i in range(16):
            assert det.observe(i, 1.0 + 0.01 * (i % 3), 0.0) is None
        flagged = 0
        for i in range(16, 48):  # the curve settles at a higher level
            if det.observe(i, 5.0 + 0.01 * (i % 3), 0.0) is not None:
                det.clear_pending()
                flagged += 1
        assert flagged > 0       # the shift itself is flagged...
        assert flagged < 20      # ...but not every shifted step forever
        assert det.observe(48, 5.0, 0.0) is None  # the gate has adapted

    def test_min_history_gates_spikes(self):
        det = AnomalyDetector(policy="rollback", min_history=8)
        for i in range(5):
            det.observe(i, 1.0, 0.0)
        assert det.observe(6, 1000.0, 0.0) is None  # window too short

    def test_warn_policy_records_without_pending(self):
        det = AnomalyDetector(policy="warn", min_history=4)
        with pytest.warns(UserWarning, match="anomaly detected"):
            det.observe(1, float("inf"), 1.0)
        assert det.pending is None and len(det.incidents) == 1

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            AnomalyDetector(policy="explode")

    def test_nonfinite_tolerance_for_scaler_managed_overflow(self):
        """An isolated overflow under a dynamic GradScaler is EXPECTED
        (scale growth probes the range); only a streak escalates."""
        det = AnomalyDetector(policy="rollback", min_history=4,
                              nonfinite_tolerance=2)
        a1 = det.observe(1, float("nan"), 1.0)
        assert a1.action == "tolerated" and det.pending is None
        det.observe(2, 1.0, 0.0)  # healthy step resets the streak
        a2 = det.observe(3, float("nan"), 1.0)
        assert a2.action == "tolerated" and det.pending is None
        det.observe(4, float("nan"), 1.0)
        a3 = det.observe(5, float("nan"), 1.0)  # 3rd consecutive: escalate
        assert a3.action == "rollback" and det.pending is a3

    def test_step_with_scaler_raises_detector_tolerance(self):
        from paddle_tpu.amp import GradScaler

        paddle.seed(7)
        net = nn.Linear(IN_DIM, N_CLS)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        det = AnomalyDetector(policy="rollback")
        assert det.nonfinite_tolerance == 0
        crit = nn.CrossEntropyLoss()
        CompiledTrainStep(net, lambda o, l: crit(o, l), opt,
                          anomaly_detector=det,
                          grad_scaler=GradScaler(init_loss_scaling=8.0))
        assert det.nonfinite_tolerance == 2

    def test_explicit_tolerance_and_static_scaler_not_overridden(self):
        """The auto-raise is for DYNAMIC scalers' expected growth-interval
        overflows only: an explicit nonfinite_tolerance=0 must be honored,
        and a static (non-dynamic) scaler — where a NaN is a genuine fault
        the scaler will never recover from — must not relax detection."""
        from paddle_tpu.amp import GradScaler

        paddle.seed(7)
        crit = nn.CrossEntropyLoss()

        def step(det, scaler):
            net = nn.Linear(IN_DIM, N_CLS)
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters())
            return CompiledTrainStep(net, lambda o, l: crit(o, l), opt,
                                     anomaly_detector=det, grad_scaler=scaler)

        det = AnomalyDetector(policy="rollback", nonfinite_tolerance=0)
        step(det, GradScaler(init_loss_scaling=8.0))
        assert det.nonfinite_tolerance == 0  # explicit 0 honored
        det2 = AnomalyDetector(policy="rollback")
        step(det2, GradScaler(init_loss_scaling=8.0,
                              use_dynamic_loss_scaling=False))
        assert det2.nonfinite_tolerance == 0  # static scaler: no relaxation

    def test_reset_history_keeps_incidents(self):
        det = AnomalyDetector(policy="rollback", min_history=2)
        det.observe(1, 1.0, 0.0)
        det.observe(2, float("nan"), 1.0)
        det.reset_history()
        assert len(det.history) == 0 and len(det.incidents) == 1


class TestCompiledStepDetection:
    def _step(self, det, seed=7):
        paddle.seed(seed)
        net = nn.Sequential(nn.Linear(IN_DIM, 16), nn.ReLU(),
                            nn.Linear(16, N_CLS))
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=net.parameters())
        crit = nn.CrossEntropyLoss()
        return CompiledTrainStep(net, lambda o, l: crit(o, l), opt,
                                 anomaly_detector=det)

    def test_healthy_trajectory_equal_with_detection(self):
        """Detection adds outputs to the step, so `on` and `off` are two
        different programs and XLA:CPU fuses them otherwise: the losses
        agree to float32 rounding (measured: steps 3 and 4 one ulp apart),
        not bit for bit. Bit-equality is asserted where both sides run the
        SAME program (the rollback replays of TestRunResilient and
        TestFitChaosMatrix)."""
        det = AnomalyDetector(policy="rollback", min_history=4)
        s_on = self._step(det)
        s_off = self._step(False)
        x, y = _mlp_data(0)
        on = np.asarray([float(s_on(x, y)) for _ in range(4)], np.float32)
        off = np.asarray([float(s_off(x, y)) for _ in range(4)], np.float32)
        s_on.drain()
        ulps = np.abs(on.view(np.int32).astype(np.int64)
                      - off.view(np.int32).astype(np.int64))
        assert ulps.max() <= 4, (on, off)
        assert det.incidents == [] and len(det.history) == 4

    def test_nan_batch_skips_update_and_detects_same_step(self):
        det = AnomalyDetector(policy="rollback", min_history=4)
        step = self._step(det)
        x, y = _mlp_data(0)
        l0 = float(step(x, y))
        params_before = [np.asarray(v) for v in step._param_vals]
        faults.arm("step.grads")  # poisons the float leaf -> NaN grads
        step(x, y)
        step.drain()
        # in-program skip: params/moments unchanged by the poisoned step
        for a, b in zip(params_before, step._param_vals):
            np.testing.assert_array_equal(a, np.asarray(b))
        assert det.pending is not None
        assert det.pending.kind == "nonfinite"
        # ... and the model still trains after clearing
        det.clear_pending()
        l2 = float(step(x, y))
        assert math.isfinite(l2) and l2 != l0

    def test_detector_property_and_flag_construction(self):
        set_flags({"anomaly_detection": True, "anomaly_policy": "warn"})
        try:
            step = self._step(None)  # None -> reads the flag
            assert step.anomaly_detector is not None
            assert step.anomaly_detector.policy == "warn"
        finally:
            set_flags({"anomaly_detection": False,
                       "anomaly_policy": "rollback"})
        assert self._step(None).anomaly_detector is None
        assert self._step(False).anomaly_detector is None


@pytest.mark.slow
class TestRunResilient:
    """Full supervisor recovery loops (compile-heavy: full tier; the quick
    tier keeps the registry/detector/step units)."""

    N = 24

    def _run(self, point=None, tmp=None, pol=None, **arm_kw):
        make_step, make_data = _make_step_factory(self.N)
        faults.reset()
        if point:
            faults.arm(point, **arm_kw)
        rep = run_resilient(make_step, make_data, self.N, str(tmp),
                            policy=pol, ckpt_every=6, feed_depth=2)
        faults.reset()
        return rep

    def test_fault_free_reference(self, tmp_path):
        rep = self._run(tmp=tmp_path)
        assert rep["status"] == "ok" and rep["rollbacks"] == 0
        assert len(rep["losses"]) == self.N
        assert all(math.isfinite(v) for v in rep["losses"].values())

    def test_nan_batch_rollback_bit_exact(self, tmp_path):
        ref = self._run(tmp=tmp_path / "ref")
        rep = self._run("step.grads", tmp=tmp_path / "chaos",
                        mode="nth", nth=10)
        assert rep["status"] == "ok" and rep["rollbacks"] == 1
        assert rep["losses"] == ref["losses"]  # bit-exact replay
        events = [e["event"] for e in rep["incidents"]]
        assert "anomaly" in events and "rollback" in events
        rb = next(e for e in rep["incidents"] if e["event"] == "rollback")
        assert rb["recovery_ms"] > 0

    def test_feeder_crash_resumes_at_cursor(self, tmp_path):
        ref = self._run(tmp=tmp_path / "ref")
        rep = self._run("feeder.collate", tmp=tmp_path / "chaos",
                        mode="nth", nth=13)
        assert rep["status"] == "ok" and rep["feeder_retries"] == 1
        assert rep["losses"] == ref["losses"]
        crash = next(e for e in rep["incidents"]
                     if e["event"] == "feeder_crash")
        assert crash["phase"] == "collate" and "FaultInjected" in crash["cause"]

    def test_killed_save_leaves_previous_committed(self, tmp_path):
        ref = self._run(tmp=tmp_path / "ref")
        rep = self._run("ckpt.before_rename", tmp=tmp_path / "chaos",
                        mode="nth", nth=2)
        assert rep["status"] == "ok" and rep["save_failures"] == 1
        assert rep["losses"] == ref["losses"]
        # the previous committed snapshot stayed loadable throughout
        mgr = elastic.CheckpointManager(str(tmp_path / "chaos"))
        assert mgr.latest() is not None
        mgr.load()

    def test_simulated_hang_restarts_from_hang_save(self, tmp_path):
        ref = self._run(tmp=tmp_path / "ref")
        rep = self._run("watchdog.hang", tmp=tmp_path / "chaos",
                        mode="nth", nth=15)
        assert rep["status"] == "ok" and rep["hang_restarts"] == 1
        assert rep["losses"] == ref["losses"]
        events = [e["event"] for e in rep["incidents"]]
        assert events.count("hang") == 1 and "restart" in events

    def test_persistent_fault_halts_with_report(self, tmp_path):
        """EVERY step poisoned: the run must end in a bounded, structured
        halt (rollback budget or no-older-snapshot), never a loop — with
        the incident report attached."""
        make_step, make_data = _make_step_factory(self.N)
        faults.reset()
        faults.arm("step.grads", mode="always")
        pol = ResiliencePolicy(max_rollbacks=2)
        with pytest.raises(ResilienceHalt) as ei:
            run_resilient(make_step, make_data, self.N, str(tmp_path),
                          policy=pol, ckpt_every=6, feed_depth=2)
        faults.reset()
        report = ei.value.report
        events = [e["event"] for e in report["incidents"]]
        assert "rollback" in events and "quarantine" in events
        assert report["rollbacks"] >= 1
        assert report["quarantined"]  # the recurring batch was quarantined

    def test_skip_batch_policy_quarantines(self, tmp_path):
        make_step, make_data = _make_step_factory(self.N)
        faults.reset()
        faults.arm("step.grads", mode="nth", nth=10)
        pol = ResiliencePolicy(anomaly="skip_batch")
        rep = run_resilient(make_step, make_data, self.N, str(tmp_path),
                            policy=pol, ckpt_every=6, feed_depth=2)
        faults.reset()
        assert rep["status"] == "ok" and rep["rollbacks"] == 0
        assert rep["quarantined"] == [9]  # nth=10 fires on step 10 = idx 9
        assert 9 not in rep["losses"]

    def test_skip_batch_continues_without_pipeline_rebuild(self, tmp_path):
        """skip_batch leaves params/step/cursor untouched (the in-program
        health skip already kept the poison out of the update), so the
        supervisor must continue the SAME input pipeline instead of
        tearing down and re-warming the feeder for every quarantined
        batch."""
        make_step, make_data = _make_step_factory(self.N)
        calls = []

        def counted_make_data(start):
            calls.append(start)
            return make_data(start)

        faults.reset()
        faults.arm("step.grads", mode="nth", nth=10)
        pol = ResiliencePolicy(anomaly="skip_batch")
        rep = run_resilient(make_step, counted_make_data, self.N,
                            str(tmp_path), policy=pol, ckpt_every=6,
                            feed_depth=2)
        faults.reset()
        assert rep["status"] == "ok" and rep["quarantined"] == [9]
        assert calls == [0]  # one pipeline for the whole run

    def test_caller_owned_incident_log_spans_runs(self, tmp_path):
        """run_resilient must not close a caller-provided IncidentLog: one
        log can span several runs (closing it would silently stop
        persisting the next run's events to the JSONL file)."""
        make_step, make_data = _make_step_factory(6)
        log = IncidentLog(str(tmp_path / "log.jsonl"))
        faults.reset()
        run_resilient(make_step, make_data, 6, str(tmp_path / "ck"),
                      ckpt_every=3, incident_log=log)
        assert log._f is not None  # still open for the next run
        log.emit("probe")
        log.close()
        lines = [json.loads(ln) for ln in open(tmp_path / "log.jsonl")]
        assert any(r["event"] == "probe" for r in lines)

    def test_incident_log_is_jsonl(self, tmp_path):
        make_step, make_data = _make_step_factory(self.N)
        log_path = str(tmp_path / "incidents.jsonl")
        faults.reset()
        faults.arm("step.grads", mode="nth", nth=10)
        rep = run_resilient(make_step, make_data, self.N,
                            str(tmp_path / "ck"), ckpt_every=6,
                            incident_log=log_path)
        faults.reset()
        lines = [json.loads(ln) for ln in open(log_path)]
        assert lines == rep["incidents"]
        for rec in lines:
            assert "ts" in rec and "event" in rec
        kinds = {r["event"] for r in lines}
        assert {"anomaly", "rollback"} <= kinds


class TestFitChaosMatrix:
    """The satellite chaos matrix: EVERY registered fault point injected
    once during a short Model.fit(auto_checkpoint=, resilience='rollback')
    run; training must complete with the fault-free per-batch loss
    trajectory (bit-exact — rollback replays the same batches from a
    bit-exact restore). Points whose sites a single-host fit never reaches
    (store.barrier, watchdog.hang) pass trivially here and are exercised
    by their dedicated tests above."""

    def _fit(self, point, ckpt_dir, arms=None, resilience="rollback",
             **fit_kw):
        set_mesh(None)
        build_mesh({"dp": 1})  # DistModel path: compiled step + DeviceFeeder
        paddle.seed(0)
        rng = np.random.RandomState(0)
        x = rng.randn(48, IN_DIM).astype(np.float32)
        y = rng.randint(0, N_CLS, (48,)).astype(np.int64)
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.model import Callback
        from paddle_tpu.io import TensorDataset

        class Rec(Callback):
            def __init__(self):
                self.losses = {}

            def on_epoch_begin(self, epoch, logs=None):
                self._e = epoch

            def on_train_batch_end(self, step, logs=None):
                if logs and "loss" in logs:
                    self.losses[(self._e, step)] = logs["loss"]

        net = nn.Sequential(nn.Linear(IN_DIM, 16), nn.ReLU(),
                            nn.Linear(16, N_CLS))
        model = Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(learning_rate=0.05,
                                            parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        faults.reset()
        if arms:
            for nm, nth in arms:
                faults.arm(nm, mode="nth", nth=nth)
        elif point is not None:
            # ckpt.* sites are hit once per SAVE (initial + 2 epoch ends):
            # nth=2 kills the epoch-0-end save; per-step sites fire mid-epoch
            faults.arm(point, mode="nth",
                       nth=2 if point.startswith("ckpt.") else 5)
        rec = Rec()
        ds = TensorDataset([x, y])
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                model.fit(ds, batch_size=8, epochs=2, verbose=0,
                          shuffle=False, auto_checkpoint=str(ckpt_dir),
                          resilience=resilience, callbacks=[rec], **fit_kw)
        finally:
            faults.reset()
        return rec.losses

    def test_chaos_matrix_covers_registry(self):
        # serving points register at import of the serving modules; pull
        # them in so the pin is deterministic whether or not another test
        # module imported paddle_tpu.serving first
        import paddle_tpu.serving.disagg  # noqa: F401
        import paddle_tpu.serving.replica  # noqa: F401
        import paddle_tpu.serving.router  # noqa: F401
        assert (sorted(CHAOS_POINTS + SERVING_CHAOS_POINTS)
                == sorted(faults.registered())), (
            "a fault point was registered without being added to a chaos "
            "matrix (CHAOS_POINTS here, SERVING_CHAOS_POINTS -> "
            "test_router.py / test_disagg.py)")

    @pytest.mark.slow
    def test_every_point_recovers_with_fault_free_trajectory(self, tmp_path):
        ref = self._fit(None, tmp_path / "ref")
        assert len(ref) == 12  # 6 batches x 2 epochs
        failures = []
        for i, point in enumerate(CHAOS_POINTS):
            got = self._fit(point, tmp_path / f"c{i}")
            if got != ref:
                failures.append((point, {k: (ref[k], got.get(k))
                                         for k in ref if ref[k] != got.get(k)}))
        assert not failures, failures

    def test_last_batch_anomaly_settles_before_fit_returns(self, tmp_path):
        """The run-ahead window must settle at epoch end: an anomaly on the
        FINAL dispatched batches (whose health buffers after_batch hadn't
        read yet) cannot escape the epoch — with policy 'halt' the fit must
        raise, not return a silently poisoned model."""
        with pytest.raises(RuntimeError, match="halt"):
            # nth=12 poisons the very last step (6 batches x 2 epochs);
            # metrics_sync_every=4 keeps the tail steps' losses deferred
            self._fit(None, tmp_path, arms=[("step.grads", 12)],
                      resilience="halt", metrics_sync_every=4)

    def test_rollback_across_epoch_boundary_replays_gap(self, tmp_path):
        """A rollback whose newest committed snapshot predates the current
        epoch (here: the epoch-0-end save was killed and swallowed as a
        resilient incident) must re-enter the epoch loop at the SNAPSHOT's
        epoch — replaying the batches between it and the anomaly instead of
        silently dropping them from training."""
        ref = self._fit(None, tmp_path / "ref")
        got = self._fit(None, tmp_path / "chaos",
                        arms=[("ckpt.before_rename", 2),  # epoch-0-end save
                              ("step.grads", 8)])         # epoch 1, step 1
        assert got == ref  # bit-exact: both epochs replayed from step 0

    def test_shuffled_loader_warns_about_positional_replay(self, tmp_path):
        """Replay/quarantine are positional; the default shuffle=True
        silently breaks the bit-exact contract — fit must say so."""
        set_mesh(None)
        build_mesh({"dp": 1})
        paddle.seed(0)
        from paddle_tpu.hapi import Model
        from paddle_tpu.io import TensorDataset

        net = nn.Linear(IN_DIM, N_CLS)
        model = Model(net)
        model.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        x, y = _mlp_data(0, batch=16)
        with pytest.warns(UserWarning, match="BY POSITION"):
            model.fit(TensorDataset([x, y]), batch_size=8, epochs=1,
                      verbose=0, shuffle=True,
                      auto_checkpoint=str(tmp_path), resilience="rollback")

    def test_rollback_policy_requires_auto_checkpoint(self):
        set_mesh(None)
        from paddle_tpu.hapi import Model
        from paddle_tpu.io import TensorDataset

        net = nn.Linear(IN_DIM, N_CLS)
        model = Model(net)
        model.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        x, y = _mlp_data(0)
        with pytest.raises(ValueError, match="auto_checkpoint"):
            model.fit(TensorDataset([x, y]), batch_size=4, epochs=1,
                      verbose=0, resilience="rollback")


class TestGradScalerSkipStreak:
    def test_warn_then_halt_and_reset(self):
        from paddle_tpu.amp import GradScaler

        set_flags({"scaler_max_consecutive_skips": 4})
        try:
            s = GradScaler(init_loss_scaling=8.0)
            s._found_inf = True
            with pytest.warns(UserWarning, match="consecutive"):
                s.update()  # streak 1... warn fires at limit//2 = 2
                s._found_inf = True
                s.update()
            # a good step resets the streak
            s._found_inf = False
            s.update()
            assert s._consecutive_skips == 0
            for _ in range(3):
                s._found_inf = True
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    s.update()
            s._found_inf = True
            with pytest.raises(FloatingPointError,
                               match="scaler_max_consecutive_skips"):
                s.update()
        finally:
            set_flags({"scaler_max_consecutive_skips": 100})

    def test_zero_disables(self):
        from paddle_tpu.amp import GradScaler

        set_flags({"scaler_max_consecutive_skips": 0})
        try:
            s = GradScaler(init_loss_scaling=8.0)
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # any warning would raise
                for _ in range(20):
                    s._found_inf = True
                    s.update()
        finally:
            set_flags({"scaler_max_consecutive_skips": 100})

    def test_compiled_step_streak_halts(self):
        """e2e: a permanently-NaN model under the compiled GradScaler path
        halts instead of skipping forever."""
        from paddle_tpu.amp import GradScaler

        set_flags({"scaler_max_consecutive_skips": 3})
        try:
            paddle.seed(7)
            net = nn.Linear(IN_DIM, N_CLS)
            # poison the weights: every step's grads are NaN from here on
            net.weight._set_value(net.weight._value * float("nan"))
            opt = paddle.optimizer.Adam(learning_rate=0.05,
                                        parameters=net.parameters())
            crit = nn.CrossEntropyLoss()
            step = CompiledTrainStep(net, lambda o, l: crit(o, l), opt,
                                     grad_scaler=GradScaler(
                                         init_loss_scaling=8.0))
            x, y = _mlp_data(0)
            with pytest.raises(FloatingPointError, match="permanently NaN"):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    for _ in range(8):
                        step(x, y)
                        step.drain()
        finally:
            set_flags({"scaler_max_consecutive_skips": 100})


class TestWatchdogThreadStacks:
    def test_diagnostics_name_where_threads_block(self):
        from paddle_tpu.distributed import watchdog

        gate = threading.Event()

        def blocked_in_a_named_place():
            gate.wait(10.0)

        t = threading.Thread(target=blocked_in_a_named_place,
                             name="stuck-worker", daemon=True)
        t.start()
        time.sleep(0.05)
        try:
            diag = watchdog.CommTaskManager().diagnostics()
            assert "threads" in diag
            mine = next(th for th in diag["threads"]
                        if th["name"] == "stuck-worker")
            joined = "\n".join(mine["stack"])
            # the dump names WHERE the thread is blocked
            assert "blocked_in_a_named_place" in joined
            assert "wait" in joined
        finally:
            gate.set()
            t.join(5)

    def test_hang_report_carries_stacks(self):
        from paddle_tpu.distributed import watchdog

        mgr = watchdog.CommTaskManager(default_timeout_s=0.1,
                                       poll_interval_s=0.02)
        seen = []
        off = watchdog.add_hang_listener(
            lambda task, diag: seen.append(diag), manager=mgr)

        class Stalled:
            def __array__(self, dtype=None):
                time.sleep(0.8)
                return np.zeros((), np.float32)

        try:
            watchdog.watch_step(Stalled(), name="stuck", timeout_s=0.1,
                                manager=mgr)
            deadline = time.time() + 5
            while not seen and time.time() < deadline:
                time.sleep(0.02)
            assert seen and "threads" in seen[0]
            assert any(th["stack"] for th in seen[0]["threads"])
        finally:
            off()
            mgr.stop()


class TestFeederCrashContext:
    def _src(self, n=6):
        for i in range(n):
            yield (np.full((2, 2), i, np.float32),)

    @pytest.mark.parametrize("point,phase", [("feeder.collate", "collate"),
                                             ("feeder.device_put",
                                              "device_put")])
    def test_crash_carries_cursor_and_phase(self, point, phase):
        faults.reset()
        faults.arm(point, mode="nth", nth=3)
        feeder = DeviceFeeder(self._src(), mesh=None, depth=2)
        got = []
        with pytest.raises(FeederWorkerError) as ei:
            for b in feeder:
                got.append(b)
        err = ei.value
        assert err.phase == phase
        assert err.batch_index == 2  # third batch (0-based) was in flight
        assert isinstance(err.__cause__, faults.FaultInjected)
        assert str(err.batch_index) in str(err) and phase in str(err)
        # batches before the crash were delivered; shutdown is clean
        assert len(got) == 2
        assert not feeder._thread.is_alive()
        faults.reset()

    def test_crash_with_full_queue_never_deadlocks_shutdown(self):
        """Worker dies while the bounded queue is FULL and the consumer
        stops reading: close() must drain and join without hanging."""
        faults.reset()
        faults.arm("feeder.collate", mode="nth", nth=4)
        feeder = DeviceFeeder(self._src(20), mesh=None, depth=2)
        next(feeder)  # consume one, then abandon the iterator
        time.sleep(0.2)  # let the worker fill the queue and crash
        t0 = time.time()
        feeder.close()
        assert time.time() - t0 < 2.0
        assert not feeder._thread.is_alive()
        faults.reset()


class TestStoreHardening:
    def test_barrier_retry_absorbs_transient_fault(self):
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore(is_master=True)
        try:
            faults.reset()
            faults.arm("store.barrier")  # one-shot: first attempt fails
            store.barrier("rb", world_size=1, timeout=5.0, rank=0,
                          retries=2, retry_backoff=0.01)
            assert faults.fired("store.barrier") == 1
        finally:
            faults.reset()
            store.close()

    def test_barrier_timeout_reports_attempts_and_ranks(self):
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore(is_master=True)
        try:
            with pytest.raises(TimeoutError) as ei:
                store.barrier("rb2", world_size=3, timeout=0.15, rank=0,
                              retries=1, retry_backoff=0.01)
            msg = str(ei.value)
            assert "2 attempt(s)" in msg
            assert "1/3 ranks arrived" in msg
            assert "missing ranks [1, 2]" in msg
        finally:
            store.close()

    def test_heartbeat_names_dead_and_live_ranks(self):
        from paddle_tpu.distributed.store import (RankHeartbeat, TCPStore,
                                                  dead_peers)

        store = TCPStore(is_master=True)
        hb = RankHeartbeat(store, "job", rank=0, interval_s=0.05)
        try:
            deadline = time.time() + 3
            while hb.beats == 0 and time.time() < deadline:
                time.sleep(0.01)
            # rank 0 beats; rank 1 never showed up
            dead = dead_peers(store, "job", world_size=2, timeout_s=10.0)
            assert dead == [{"rank": 1, "age_s": None}]
            # die WITHOUT the clean-exit tombstone: rank 0 goes stale and
            # is NAMED, with its staleness age
            hb.stop(mark_clean=False)
            time.sleep(0.12)
            dead = dead_peers(store, "job", world_size=2, timeout_s=0.1)
            ranks = [d["rank"] for d in dead]
            assert ranks == [0, 1]
            assert dead[0]["age_s"] is not None and dead[0]["age_s"] > 0.1
        finally:
            hb.stop()
            store.close()

    def test_heartbeat_clean_stop_is_not_a_corpse(self):
        from paddle_tpu.distributed.store import (RankHeartbeat, TCPStore,
                                                  dead_peers)

        store = TCPStore(is_master=True)
        try:
            hb = RankHeartbeat(store, "job2", rank=0, interval_s=0.05)
            deadline = time.time() + 3
            while hb.beats == 0 and time.time() < deadline:
                time.sleep(0.01)
            hb.stop()  # writes the +inf tombstone: a clean exit...
            time.sleep(0.12)
            dead = dead_peers(store, "job2", world_size=1, timeout_s=0.05)
            assert dead == []  # ...is never reported dead, even when stale
        finally:
            store.close()

    def test_dead_peers_watch_is_clock_skew_immune(self):
        """On a real pod the beat payload is the REMOTE host's wall clock;
        with `watch`, staleness is local time since the value last CHANGED,
        so an NTP-skewed peer neither reads as a permanent corpse (clock
        behind) nor masks a real death (clock ahead)."""
        import struct as _struct

        from paddle_tpu.distributed.store import TCPStore, dead_peers

        store = TCPStore(is_master=True)
        key = "__hb__/skew/0"
        try:
            # a peer whose clock lags by ~1h: the stateless comparison
            # names a live, beating rank as a corpse...
            store.set(key, _struct.pack("<d", time.time() - 3600.0))
            assert [d["rank"] for d in
                    dead_peers(store, "skew", 1, timeout_s=10.0)] == [0]
            # ...but a watch dict sees the VALUE move: alive
            watch = {}
            dead_peers(store, "skew", 1, timeout_s=0.1, watch=watch)
            store.set(key, _struct.pack("<d", time.time() - 3599.0))
            time.sleep(0.15)
            assert dead_peers(store, "skew", 1, timeout_s=0.1,
                              watch=watch) == []
            # frozen value: after timeout_s of LOCAL time it IS a corpse,
            # even though its future-dated stamp still looks fresh...
            store.set(key, _struct.pack("<d", time.time() + 3600.0))
            dead_peers(store, "skew", 1, timeout_s=0.1, watch=watch)
            time.sleep(0.15)
            assert [d["rank"] for d in
                    dead_peers(store, "skew", 1, timeout_s=0.1,
                               watch=watch)] == [0]
            # ...a corpse the stateless comparison masks entirely
            assert dead_peers(store, "skew", 1, timeout_s=10.0) == []
        finally:
            store.close()


class TestExceptPassLint:
    """Tier-1 lint: a bare `except ...: pass` swallows the very failures
    the resilience layer exists to surface. Every handler whose body is
    exactly `pass` must be allowlisted (tools/except_pass_allowlist.txt)
    with the file + except-line — so new swallowing shows up in review."""

    ALLOWLIST = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                             "except_pass_allowlist.txt")

    def _offenders(self):
        import ast

        import paddle_tpu

        root = os.path.dirname(paddle_tpu.__file__)
        repo = os.path.dirname(root)
        out = set()
        for dirpath, dirnames, files in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                if not f.endswith(".py"):
                    continue
                p = os.path.join(dirpath, f)
                src = open(p).read()
                try:
                    tree = ast.parse(src)
                except SyntaxError:
                    continue
                lines = src.splitlines()
                rel = os.path.relpath(p, repo)
                for node in ast.walk(tree):
                    if (isinstance(node, ast.ExceptHandler)
                            and len(node.body) == 1
                            and isinstance(node.body[0], ast.Pass)):
                        out.add(f"{rel} :: "
                                f"{lines[node.lineno - 1].strip()}")
        return out

    def test_no_unallowlisted_exception_swallowing(self):
        allow = set()
        with open(self.ALLOWLIST) as f:
            for line in f:
                line = line.strip()
                if line and not line.startswith("#"):
                    allow.add(line)
        offenders = self._offenders()
        new = sorted(offenders - allow)
        assert not new, (
            "new `except ...: pass` swallowing (handle the error, report "
            "it, or add a reviewed entry to tools/except_pass_allowlist"
            ".txt):\n" + "\n".join(new))
        stale = sorted(allow - offenders)
        assert not stale, (
            "stale allowlist entries (the handler was fixed/moved — prune "
            "them so the allowlist stays honest):\n" + "\n".join(stale))


class TestRegistryCoverage:
    def test_every_registered_point_is_exercised_by_tests(self):
        """Every registered fault point must appear (as a literal) in at
        least one test module — an injection point nobody chaos-tests is
        dead weight that will silently rot."""
        # the site modules register at import; make sure they're all in
        import paddle_tpu.distributed.checkpoint.elastic  # noqa: F401
        import paddle_tpu.distributed.resilience.supervisor  # noqa: F401
        import paddle_tpu.distributed.store  # noqa: F401
        import paddle_tpu.io.device_feed  # noqa: F401
        import paddle_tpu.parallel.train_step  # noqa: F401
        import paddle_tpu.serving.replica  # noqa: F401
        import paddle_tpu.serving.router  # noqa: F401

        tests_dir = os.path.dirname(__file__)
        corpus = ""
        for f in os.listdir(tests_dir):
            if f.endswith(".py"):
                corpus += open(os.path.join(tests_dir, f)).read()
        uncovered = [p for p in faults.registered() if p not in corpus]
        assert not uncovered, (
            f"registered fault points never exercised by any test: "
            f"{uncovered}")
