"""Disaggregated prefill/decode (PR 19): batched packed prefill parity
(N short prompts in ONE segment-id flash frame -> page contents + decode
streams bit-equal to N sequential prefills, fp32 + bf16 GQA through the
interpret kernels), zero-retrace across packing mixes, the KV-page
handoff in both alias and copy modes, exactly-once recovery under the
`serving.prefill.kill` / `serving.handoff.drop` chaos points, role-aware
router placement, and the HTTP replica transport run through the same
router matrix as InProcessReplica (failover, breaker, queue-full
exclusion, drain) against a live serve.py endpoint."""
import json
import queue as queue_mod
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import set_flags
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.disagg import (HandoffChannel, PrefillWorker,
                                       build_disagg)
from paddle_tpu.serving.replica import HTTPReplica, ReplicaDead, StreamCut
from paddle_tpu.serving.router import Router

from test_router import (FakeEngine, ScriptedReplica, _cfg, _expected,
                         _payload)


def _model(**over):
    paddle.seed(0)
    cfg = llama_tiny_config(**over)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, **over):
    kw = dict(page_size=4, num_pages=64, decode_batch=4, prefill_chunk=32,
              max_seq_len=64)
    kw.update(over)
    return ServingEngine(m, ServingConfig(**kw))


def _prompts(rng, cfg, lens):
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _residue_free(eng):
    """Exactly-once postcondition: nothing half-admitted anywhere."""
    assert eng.scheduler._by_rid == {}
    assert eng._pending_handoff == {}
    assert eng._cancelled_pending == set()
    assert eng.allocator.used_pages == 0
    eng.allocator.check_consistency()


@contextmanager
def _disagg(eng, n_workers=1, mode="alias", timeout_s=None):
    channel, workers = build_disagg(eng, n_workers, mode=mode,
                                    timeout_s=timeout_s)
    try:
        yield channel, workers
    finally:
        for w in workers:
            w.close()
        eng._handoff_channel = None


# ONE shared model + engine pair for the non-kernel tests: `seq` prefills
# one request at a time (the PR-18 path — pack_frame floors at 32, where
# every 32-aligned segment fills a whole frame and the chunked path runs),
# `pack` batches admissions into [1, 64] segment-id frames. Each extra
# engine costs fresh XLA compiles, so tests must leave both idle.
@pytest.fixture(scope="module")
def shared():
    m, cfg = _model()
    seq = _engine(m, prefill_pack=False)
    pack = _engine(m, pack_frame=64)
    return m, cfg, seq, pack


# ---------------------------------------------------------------------------
# packed multi-prompt prefill: bit-parity + zero-retrace
# ---------------------------------------------------------------------------

def _chain_pages(eng, rid, n_tokens):
    """Per-request KV bytes for the first ``n_tokens`` positions, gathered
    chain-position by chain-position so parity doesn't depend on page-id
    assignment. Slack positions past ``n_tokens`` are excluded: the chunked
    sequential path scatters pad-token garbage there while the packed path
    leaves pool zeros, and neither is ever read back."""
    chain = eng.allocator.chain(rid)
    out = {}
    for name, arr in eng._cache.items():
        a = np.asarray(arr)[:, :, chain]        # [L, H, P, page_size, D]
        toks = a.reshape(a.shape[0], a.shape[1], -1, a.shape[-1])
        out[name] = toks[:, :, :n_tokens]
    return out


def _bf16_ulps_apart(a, b):
    """Elementwise distance of two bfloat16 arrays in representable values
    (sign-magnitude bit patterns mapped onto one ordered integer line)."""
    def ordinal(x):
        u = np.ascontiguousarray(x).view(np.uint16).astype(np.int32)
        return np.where(u & 0x8000, -(u & 0x7FFF), u)
    return np.abs(ordinal(a) - ordinal(b))


def _packed_vs_sequential(m, cfg, lens, n_new, pack_frame=64,
                          bf16_last_ulp=False):
    """Submit the same prompts to a sequential-prefill engine and a
    packed-prefill engine, compare page contents after the first step and
    the full greedy streams after completion. Returns the pack engine.

    Pages are compared bit for bit. `bf16_last_ulp` is for bfloat16 pools
    only: the [1, 64] packed frame and the [1, 32] sequential frames are
    two different XLA programs, and XLA:CPU may round a float32
    intermediate to bfloat16 at another point of the fused projection, so
    a value can land on the neighbouring bfloat16. There the pools must
    agree to ONE bfloat16 ulp with at most 0.1% of a pool's elements off;
    the greedy streams stay exactly equal either way."""
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, cfg, lens)
    seq = _engine(m, prefill_pack=False)
    pack = _engine(m, pack_frame=pack_frame)
    rids = {}
    for eng in (seq, pack):
        rids[eng] = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        eng.step()                       # admission: prefill + first token
    assert pack.stats()["prefill_packed_frames"] >= 1, \
        "packing never engaged — the parity run is vacuous"
    for rs, rp, n in zip(rids[seq], rids[pack], lens):
        sp, pp = _chain_pages(seq, rs, n), _chain_pages(pack, rp, n)
        for name in sp:
            if bf16_last_ulp:
                apart = _bf16_ulps_apart(sp[name], pp[name])
                assert apart.max() <= 1 and (apart > 0).mean() <= 1e-3, (
                    f"packed prefill diverged from sequential in pool "
                    f"{name!r}: {int((apart > 0).sum())} of {apart.size} "
                    f"elements, up to {int(apart.max())} bfloat16 ulps")
            else:
                assert np.array_equal(sp[name], pp[name]), (
                    f"packed prefill diverged from sequential in pool "
                    f"{name!r}")
    outs = {}
    for eng in (seq, pack):
        eng.run_until_idle()
        outs[eng] = [list(eng.scheduler.get(r).generated)
                     for r in rids[eng]]
        for r in rids[eng]:
            eng.release(r)
        _residue_free(eng)
    assert outs[seq] == outs[pack]
    return pack


class TestPackedPrefillParity:
    def test_fp32_parity_through_interpret_kernels(self, flash_interpret,
                                                   paged_interpret):
        # pin the flash tiles to the 32-row pack alignment so the packed
        # [1, 64] frame decomposes into the SAME blocks as the [1, 32]
        # sequential frames (bit-equality is block-decomposition parity);
        # 17..32-token prompts occupy one full 32-aligned segment each
        set_flags({"flash_block_q": 32, "flash_block_k": 32})
        try:
            m, cfg = _model()
            _packed_vs_sequential(m, cfg, (17, 23, 32, 19), n_new=3)
        finally:
            set_flags({"flash_block_q": 0, "flash_block_k": 0})

    def test_bf16_gqa_parity_through_interpret_kernels(self, flash_interpret,
                                                       paged_interpret):
        set_flags({"flash_block_q": 32, "flash_block_k": 32})
        try:
            m, cfg = _model(num_key_value_heads=2)
            m.to(dtype="bfloat16")
            _packed_vs_sequential(m, cfg, (18, 29), n_new=2,
                                  bf16_last_ulp=True)
        finally:
            set_flags({"flash_block_q": 0, "flash_block_k": 0})

    def test_parity_on_xla_fallback(self, shared):
        """The same contract off the kernels (XLA reference attention):
        masked cross-segment scores are exact zeros, so streams match
        bit-for-bit on any backend."""
        m, cfg, seq, pack = shared
        rng = np.random.RandomState(3)
        prompts = _prompts(rng, cfg, (7, 5, 9, 6, 12))
        ref = seq.generate(prompts, max_new_tokens=4)
        got = pack.generate(prompts, max_new_tokens=4)
        assert got == ref
        assert pack.stats()["prefill_packed_requests"] >= 4
        _residue_free(seq)
        _residue_free(pack)

    def test_zero_retrace_across_packing_mixes(self, shared):
        m, cfg, _, pack = shared
        rng = np.random.RandomState(5)
        # warm every program this test's mixes can reach: a 2-seg packed
        # frame AND the chunked fallback (odd leftover -> single frame)
        pack.generate(_prompts(rng, cfg, (5, 6, 7)), max_new_tokens=2)
        pack.mark_warmup()
        traces = pack.prefill_traces
        for mix in ((9, 3), (10, 4, 6, 5), (8,), (13, 2, 7)):
            pack.generate(_prompts(rng, cfg, mix), max_new_tokens=3)
        assert pack.decode_retraces_after_warmup == 0
        assert pack.prefill_traces == traces, \
            "a packing mix retraced a prefill program"
        _residue_free(pack)

    def test_fill_gauge_and_role_in_stats(self, shared):
        m, cfg, _, pack = shared
        rng = np.random.RandomState(6)
        pack.generate(_prompts(rng, cfg, (5, 6, 7, 9)), max_new_tokens=2)
        st = pack.stats()
        assert st["role"] == "mixed"
        assert 0.0 < st["prefill_batch_fill"] <= 1.0
        assert st["prefill_packed_frames"] >= 1
        _residue_free(pack)

    def test_role_validation(self, shared):
        m, _, _, _ = shared
        with pytest.raises(ValueError, match="role"):
            _engine(m, role="bogus")
        eng = _engine(m, role="decode")
        assert eng.stats()["role"] == "decode"


# ---------------------------------------------------------------------------
# the KV-page handoff: alias + copy modes, exactly-once chaos
# ---------------------------------------------------------------------------

class TestHandoff:
    LENS = (7, 5, 9, 6)

    def _reference(self, shared):
        m, cfg, seq, _ = shared
        rng = np.random.RandomState(9)
        prompts = _prompts(rng, cfg, self.LENS)
        return prompts, seq.generate(prompts, max_new_tokens=4)

    def test_alias_handoff_stream_parity(self, shared):
        m, cfg, seq, pack = shared
        prompts, ref = self._reference(shared)
        h0 = pack.stats()["handoffs"]
        with _disagg(pack) as (channel, _):
            assert pack.generate(prompts, max_new_tokens=4) == ref
            st = pack.stats()
            assert st["handoffs"] - h0 == len(prompts)
            assert st["handoff_pages"] > 0
            assert st["pending_handoffs"] == 0
            assert channel.stats()["delivered"] >= len(prompts)
        _residue_free(pack)

    def test_copy_handoff_stream_parity(self, shared):
        m, cfg, seq, pack = shared
        prompts, ref = self._reference(shared)
        with _disagg(pack, mode="copy"):
            assert pack.generate(prompts, max_new_tokens=4) == ref
        _residue_free(pack)

    def test_prefill_kill_reclaims_bit_equal(self, shared):
        """Kill a prefill worker mid-handoff (after the device prefill,
        before delivery): the decode side re-prefills locally — zero lost
        streams, bit-equal to fault-free, zero residue."""
        m, cfg, seq, pack = shared
        prompts, ref = self._reference(shared)
        r0 = pack.stats()["handoff_reclaims"]
        faults.reset()
        try:
            faults.arm("serving.prefill.kill")
            with _disagg(pack, timeout_s=0.5) as (channel, workers):
                assert pack.generate(prompts, max_new_tokens=4) == ref
                assert faults.fired("serving.prefill.kill") == 1
                assert not workers[0].alive
                assert workers[0].dead_cause is not None
            assert pack.stats()["handoff_reclaims"] > r0
        finally:
            faults.reset()
        _residue_free(pack)

    def test_handoff_drop_times_out_and_reclaims(self, shared):
        m, cfg, seq, pack = shared
        prompts, ref = self._reference(shared)
        faults.reset()
        try:
            faults.arm("serving.handoff.drop")
            with _disagg(pack, timeout_s=0.25) as (channel, _):
                assert pack.generate(prompts, max_new_tokens=4) == ref
                assert faults.fired("serving.handoff.drop") == 1
                assert channel.stats()["dropped"] == 1
            assert pack.stats()["handoff_reclaims"] >= 1
        finally:
            faults.reset()
        _residue_free(pack)

    def test_cancel_during_pending_handoff_defers_release(self, shared):
        """cancel() on a request parked on the prefill workers must not
        free pages a worker may still be writing: the release defers to
        handoff resolution on the decode thread."""
        m, cfg, seq, pack = shared
        rng = np.random.RandomState(13)
        faults.reset()
        try:
            faults.arm("serving.handoff.drop")   # keep the job pending
            with _disagg(pack, timeout_s=0.2):
                rid = pack.submit(_prompts(rng, cfg, (6,))[0],
                                  max_new_tokens=8)
                pack.step()
                assert rid in pack._pending_handoff \
                    or pack.scheduler._by_rid.get(rid) is not None
                assert pack.cancel(rid)
                pack.run_until_idle()
        finally:
            faults.reset()
        _residue_free(pack)


# ---------------------------------------------------------------------------
# role-aware router placement
# ---------------------------------------------------------------------------

class TestRoleAwarePlacement:
    def test_prefill_role_never_takes_dispatches(self):
        pre = ScriptedReplica(0)
        pre.probe_result = {"ok": True, "role": "prefill",
                            "queue_depth": 0, "slot_fill": 0.0}
        dec = ScriptedReplica(1)
        dec.probe_result = {"ok": True, "role": "decode",
                            "queue_depth": 0, "slot_fill": 0.0}
        r = Router([pre, dec], _cfg(), start_monitor=False)
        try:
            r.monitor_tick()
            for i in range(3):
                p = np.arange(1 + i, 6 + i)
                toks, term = r.generate(_payload(p))
                assert toks == _expected(p, 5) and term["done"]
            assert pre.payloads == []           # never dispatched to
            assert len(dec.payloads) == 3
            snap = r.stats()["replicas"]
            assert snap["0"]["role"] == "prefill"
            assert snap["1"]["role"] == "decode"
        finally:
            r.close()


# ---------------------------------------------------------------------------
# the HTTP replica transport against live serve.py endpoints
# ---------------------------------------------------------------------------

def _serve_fake(eng, admit_fn=None, cut_after=None, role="mixed"):
    """A live serve.py endpoint over a FakeEngine: the same ndjson
    /generate + /healthz + /stats protocol ServingEngine.serve_http
    speaks, with a deterministic token function so routed streams have an
    exact expected value. Returns (servers-to-close, port)."""
    from paddle_tpu.inference.serve import build_http_server

    lock = threading.Lock()
    stop = threading.Event()

    def generate_fn(payload, deadline):
        q = queue_mod.Queue()
        with lock:
            rid = eng.submit(np.asarray(payload["prompt_ids"], np.int32),
                             max_new_tokens=int(
                                 payload.get("max_new_tokens", 16)),
                             stream_cb=lambda req, tok: q.put(tok))
            req = eng.scheduler.get(rid)
        n = 0
        try:
            while True:
                if time.monotonic() > deadline:
                    yield {"rid": rid, "error": "timeout"}
                    return
                try:
                    tok = q.get(timeout=0.02)
                except queue_mod.Empty:
                    if req.finished and q.empty():
                        break
                    continue
                if cut_after is not None and n >= cut_after:
                    raise RuntimeError("injected transport fault")
                n += 1
                yield {"rid": rid, "token": int(tok)}
                if req.finished and q.empty():
                    break
            yield {"rid": rid, "done": True, "tokens": n}
        finally:
            with lock:
                if not req.finished:
                    eng.cancel(rid)
                eng.release(rid)

    def drive():
        while not stop.is_set():
            with lock:
                busy = not eng.scheduler.idle
                if busy:
                    eng.step()
            if not busy:
                time.sleep(0.002)

    srv = build_http_server(
        0, generate_fn=generate_fn, queue_limit=32, timeout_s=30.0,
        max_body_bytes=1 << 20, admit_fn=admit_fn,
        health_fn=lambda: {"ok": True, "role": role, **eng.stats()},
        stats_fn=eng.stats)
    threads = [
        threading.Thread(target=srv.serve_forever, daemon=True,
                         name="paddle_tpu.serving.test.http"),
        threading.Thread(target=drive, daemon=True,
                         name="paddle_tpu.serving.test.driver"),
    ]
    for t in threads:
        t.start()

    def close():
        stop.set()
        srv.shutdown()
        srv.server_close()
        for t in threads:
            t.join(timeout=5.0)

    return close, srv.server_address[1]


@contextmanager
def _http_fleet(n=2, cut_after=None, admit0=None, step_delay_s=0.0):
    """N live serve.py endpoints wrapped in HTTPReplica transports.
    `cut_after`/`admit0` apply to endpoint 0 only (the fault target)."""
    engines = [FakeEngine(step_delay_s=step_delay_s) for _ in range(n)]
    closers, reps = [], []
    try:
        for i, eng in enumerate(engines):
            close, port = _serve_fake(
                eng,
                admit_fn=admit0 if i == 0 else None,
                cut_after=cut_after if i == 0 else None)
            closers.append(close)
            reps.append(HTTPReplica("127.0.0.1", port, replica_id=i,
                                    timeout_s=5.0))
        yield engines, reps, closers
    finally:
        for close in closers:
            close()


class TestHTTPReplicaMatrix:
    def test_probe_and_stream_roundtrip(self):
        with _http_fleet(n=1) as (engines, reps, _):
            rep = reps[0]
            pr = rep.probe()
            assert pr["ok"] is True and pr["replica"] == 0
            for k in ("queue_depth", "slot_fill", "free_pages"):
                assert k in pr, k
            p = np.arange(2, 8)
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
            # the endpoint's finally-block released engine bookkeeping
            deadline = time.time() + 2.0
            while engines[0].scheduler._by_rid and time.time() < deadline:
                time.sleep(0.01)
            assert engines[0].scheduler._by_rid == {}
            assert engines[0].allocator.used_pages == 0

    def test_dead_endpoint_probe_raises_replica_dead(self):
        with _http_fleet(n=1) as (_, reps, closers):
            closers[0]()
            closers.clear()            # already closed: skip double-close
            with pytest.raises(ReplicaDead):
                reps[0].probe()
            with pytest.raises(ReplicaDead):
                reps[0].open_stream(_payload(np.arange(1, 4)))

    def test_mid_stream_fault_fails_over_exactly_once(self):
        """Endpoint 0's stream dies after 2 tokens (the server surfaces
        the fault as a terminal error event): the router must fail over
        and the client still sees every token exactly once."""
        with _http_fleet(n=2, cut_after=2) as (_, reps, _c):
            r = Router(reps, _cfg(gap_timeout_s=2.0), start_monitor=False)
            try:
                p = np.arange(3, 9)
                toks, term = r.generate(_payload(p, n=6))
                assert toks == _expected(p, 6)
                assert term["done"] and term["failovers"] == 1
                assert term["replica"] == 1
                assert r._inflight == {}
            finally:
                r.close()

    def test_stream_cut_chaos_point_fails_over(self):
        """The PR-11 transport chaos point fires inside the HTTP stream
        reader exactly as it does for InProcessReplica."""
        with _http_fleet(n=2) as (_, reps, _c):
            r = Router(reps, _cfg(gap_timeout_s=2.0), start_monitor=False)
            faults.reset()
            try:
                faults.arm("serving.stream.cut")
                p = np.arange(5, 11)
                toks, term = r.generate(_payload(p, n=5))
                assert toks == _expected(p, 5)
                assert term["done"] and term["failovers"] == 1
                assert faults.fired("serving.stream.cut") == 1
            finally:
                faults.reset()
                r.close()

    def test_dead_endpoint_trips_breaker_routes_to_peer(self):
        with _http_fleet(n=2) as (_, reps, closers):
            r = Router(reps, _cfg(failure_threshold=2),
                       start_monitor=False)
            try:
                closers[0]()
                closers.pop(0)
                r.monitor_tick()
                r.monitor_tick()
                snap = r.stats()["replicas"]
                assert snap["0"]["circuit"] == "open"
                p = np.arange(4, 9)
                toks, term = r.generate(_payload(p))
                assert toks == _expected(p, 5)
                assert term["replica"] == 1 and term["failovers"] == 0
            finally:
                r.close()

    def test_queue_full_503_excluded_without_breaker_strike(self):
        refuse = {"status": 503, "retry_after": 0.1,
                  "message": "queue full"}
        with _http_fleet(n=2, admit0=lambda payload: refuse) \
                as (_, reps, _c):
            r = Router(reps, _cfg(), start_monitor=False)
            try:
                p = np.arange(6, 11)
                toks, term = r.generate(_payload(p))
                assert toks == _expected(p, 5)
                assert term["replica"] == 1
                snap = r.stats()["replicas"]
                # backpressure is load, not ill health: no strike, no trip
                assert snap["0"]["consecutive_failures"] == 0
                assert snap["0"]["circuit"] == "closed"
            finally:
                r.close()

    def test_drain_mid_stream_fails_over(self):
        with _http_fleet(n=2, step_delay_s=0.01) as (_, reps, _c):
            r = Router(reps, _cfg(gap_timeout_s=2.0), start_monitor=False)
            try:
                p = np.arange(2, 9)
                out = {}

                def client():
                    out["r"] = r.generate(_payload(p, n=24))

                t = threading.Thread(target=client)
                t.start()
                # with empty probes, least-loaded placement picks rid 0;
                # wait until the stream is live, then drain it away
                deadline = time.time() + 5.0
                while not r._inflight and time.time() < deadline:
                    time.sleep(0.005)
                r.drain(0, why="maintenance")
                t.join(timeout=30.0)
                toks, term = out["r"]
                assert toks == _expected(p, 24)    # exactly once
                assert term["done"] and term["failovers"] == 1
                assert r.stats()["drained"] >= 1
                r.undrain(0)
            finally:
                r.close()
