"""Multi-tenant LoRA, tier-1: adapter train->export->serve.

Covers the whole adapter lifecycle against one tiny Llama: frozen-base
training parity (the adapter must learn while the base stays bit-frozen
and optimizer state stays adapter-sized), artifact round-trip (incl.
bfloat16 factors; adapter containers carry no stablehlo program),
heterogeneous continuous batching (a mixed-tenant batch must be
BIT-EQUAL to serving each tenant alone, with zero decode retraces across
any adapter mix), AdapterStore paging (LRU eviction, refcount pinning,
hot-swap under live traffic), the `serving.lora.swap_fail` chaos point
(typed per-request error, never a wedged stream), and router tenancy
(adapter-affinity placement, per-tenant in-flight caps, no breaker
strike for an adapter load failure).

ONE module-scope model + store + engine amortizes the prefill/decode
compile (~5 s on the CI box) across every serving test — the shared
engine doubles as the zero-retrace witness, since `mark_warmup()` runs
once at fixture build and every later mix asserts the counter stayed 0.
"""
import os
import zipfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.lora import (AdapterStore, LoRAConfig, attach, detach,
                             export_adapter, load_adapter)
from paddle_tpu.lora.store import AdapterLoadError
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.engine import ServingConfig, ServingEngine

RANK = 4


def _config(**over):
    kw = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=4, max_position_embeddings=128,
              use_parallel_cross_entropy=False)
    kw.update(over)
    return LlamaConfig(**kw)


def _mk_adapter(m, path, aid, seed, scale=0.05, dtype=None):
    """Fabricate a distinct non-trivial adapter without training: attach,
    randomize B (export writes whatever is attached), export, detach —
    detach restores the model bit-exactly, so fabrication never leaks
    into later tests."""
    h = attach(m, LoRAConfig(rank=RANK, alpha=2.0 * RANK, seed=seed,
                             dtype=dtype))
    r = np.random.default_rng(seed)
    for _, _, _, B in h.entries:
        B.set_value((r.standard_normal(tuple(B.shape)) * scale)
                    .astype(np.asarray(B._value).dtype))
    export_adapter(path, h, adapter_id=aid)
    detach(h)
    return h


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """model + AdapterStore(4 slots) + ServingEngine, compiled + warmed
    ONCE (a mixed adapter/base batch), `mark_warmup()` armed: every test
    after this shares the compile and extends the zero-retrace window."""
    d = tmp_path_factory.mktemp("adapters")
    paddle.seed(0)
    m = LlamaForCausalLM(_config())
    m.eval()
    for aid, seed in (("ten-a", 7), ("ten-b", 13)):
        _mk_adapter(m, str(d / f"{aid}.pdmodel"), aid, seed)
    store = AdapterStore(m, rank=RANK, slots=4)
    store.register("ten-a", str(d / "ten-a.pdmodel"))
    store.register("ten-b", str(d / "ten-b.pdmodel"))
    eng = ServingEngine(m, ServingConfig(page_size=16, num_pages=64,
                                         decode_batch=4, prefill_chunk=16,
                                         max_seq_len=64),
                        adapter_store=store)
    rids = [eng.submit(np.arange(3, 9, dtype=np.int32), max_new_tokens=4,
                       adapter="ten-a", tenant="ten-a"),
            eng.submit(np.arange(20, 26, dtype=np.int32), max_new_tokens=4)]
    eng.run_until_idle()
    for r in rids:
        eng.release(r)
    eng.mark_warmup()
    return m, store, eng, d


def _drain(eng, rid):
    eng.run_until_idle()
    out = list(eng.scheduler.get(rid).generated)
    eng.release(rid)
    return out


class TestTraining:
    def test_adapter_learns_frozen_base_stays_put(self):
        """Adapter-vs-full-finetune parity on a toy overfit target: the
        rank-4 adapter must recover a meaningful share of the full
        fine-tune's loss drop while the frozen base stays bit-identical
        and optimizer state covers the A/B factors ONLY."""
        from paddle_tpu.parallel.train_step import CompiledTrainStep

        def run(lora: bool):
            paddle.seed(0)
            m = LlamaForCausalLM(_config())
            snap = {id(p): np.asarray(p._value).copy()
                    for p in m.parameters()}
            h = attach(m, LoRAConfig(rank=RANK, alpha=2.0 * RANK,
                                     seed=1)) if lora else None
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = CompiledTrainStep(m, lambda out, lab: out, optimizer=opt)
            rng = np.random.RandomState(0)
            ids = paddle.to_tensor(
                rng.randint(0, 128, (2, 16)).astype(np.int64))
            labels = paddle.to_tensor(
                rng.randint(0, 128, (2, 16)).astype(np.int64))
            l0 = float(step(ids, labels, labels))
            for _ in range(10):
                ln = float(step(ids, labels, labels))
            step.sync_params_to_model()
            step.sync_states_to_optimizer()
            return m, h, snap, step, l0, ln

        m, h, snap, step, l0, ln = run(lora=True)
        assert ln < l0                                 # the adapter learns
        n_factors = 2 * len(h.entries)
        trainable = [p for p in m.parameters() if not p.stop_gradient]
        assert len(trainable) == n_factors
        # frozen-base invariance: training + sync moved NO base weight
        for p in m.parameters():
            if p.stop_gradient:
                assert np.array_equal(np.asarray(p._value), snap[id(p)])
        # optimizer state is sized to the adapter, not the model
        assert sum(1 for st in step._opt_states if st) == n_factors
        lora_drop = l0 - ln

        detach(h)
        for p in m.parameters():       # detach restores bit-exactly
            assert np.array_equal(np.asarray(p._value), snap[id(p)])

        _, _, _, step_f, f0, fn = run(lora=False)
        assert sum(1 for st in step_f._opt_states if st) > n_factors
        full_drop = f0 - fn
        # parity on the toy target: same seeds/data, so deterministic
        assert lora_drop > 0.25 * full_drop > 0

    def test_artifact_round_trip(self, tmp_path):
        paddle.seed(0)
        m = LlamaForCausalLM(_config())
        p = str(tmp_path / "rt.pdmodel")
        h = attach(m, LoRAConfig(rank=RANK, alpha=8.0, seed=3))
        r = np.random.default_rng(3)
        want = []
        for _, _, A, B in h.entries:
            B.set_value((r.standard_normal(tuple(B.shape)) * 0.1)
                        .astype(np.float32))
            want.append((np.asarray(A._value).copy(),
                         np.asarray(B._value).copy()))
        export_adapter(p, h, adapter_id="acme")
        detach(h)

        blob = load_adapter(p)
        meta = blob["adapter"]
        assert meta["id"] == "acme" and int(meta["rank"]) == RANK
        assert float(meta["alpha"]) == 8.0
        assert len(meta["names"]) == len(want)
        for name, (wa, wb) in zip(meta["names"], want):
            a, b = blob["weights"][name]
            assert np.array_equal(np.asarray(a), wa)
            assert np.array_equal(np.asarray(b), wb)
        # adapters are pure data against a shared base: tiny, no program
        assert os.path.getsize(p) < 64 * 1024
        assert "stablehlo.bin" not in zipfile.ZipFile(p).namelist()

    def test_artifact_round_trip_bf16(self, tmp_path):
        import ml_dtypes

        paddle.seed(0)
        m = LlamaForCausalLM(_config())
        p = str(tmp_path / "bf16.pdmodel")
        _mk_adapter(m, p, "bf", seed=5, dtype="bfloat16")
        blob = load_adapter(p)
        for a, b in blob["weights"].values():
            assert np.asarray(a).dtype == ml_dtypes.bfloat16
            assert np.asarray(b).dtype == ml_dtypes.bfloat16
        # and a store accepts bf16 factors (cast to its pool dtype)
        store = AdapterStore(m, rank=RANK, slots=1)
        store.register("bf", p)

    def test_non_adapter_artifact_rejected(self, tmp_path):
        from paddle_tpu.inference.artifact import write_artifact

        p = str(tmp_path / "plain.pdmodel")
        write_artifact(p, {"params": [np.zeros((2, 2), np.float32)]})
        with pytest.raises(ValueError, match="adapter"):
            load_adapter(p)


class TestHeterogeneousServing:
    def test_mixed_batch_bit_equal_to_sequential(self, served):
        """THE tentpole contract: three tenants (two adapters + base) in
        one continuous batch decode the exact token streams each would
        get served alone — and nothing about the mix retraces."""
        m, store, eng, _ = served
        prompts = [np.arange(3, 9, dtype=np.int32),
                   np.arange(20, 30, dtype=np.int32),
                   np.arange(40, 44, dtype=np.int32)]
        adapters = ["ten-a", "ten-b", None]

        rids = [eng.submit(p, max_new_tokens=8, adapter=a, tenant=a or "")
                for p, a in zip(prompts, adapters)]
        eng.run_until_idle()
        het = [list(eng.scheduler.get(r).generated) for r in rids]
        for r in rids:
            eng.release(r)

        seq = []
        for p, a in zip(prompts, adapters):
            rid = eng.submit(p, max_new_tokens=8, adapter=a)
            seq.append(_drain(eng, rid))
        assert het == seq
        assert eng.decode_retraces_after_warmup == 0

    def test_adapter_actually_changes_output(self, served):
        m, store, eng, _ = served
        p = np.arange(3, 9, dtype=np.int32)
        with_a = _drain(eng, eng.submit(p, max_new_tokens=8,
                                        adapter="ten-a"))
        base = _drain(eng, eng.submit(p, max_new_tokens=8))
        assert with_a != base          # the delta is live, not a no-op

    def test_zero_retrace_across_mixes(self, served):
        m, store, eng, _ = served
        p = np.arange(5, 11, dtype=np.int32)
        mixes = [[None, None], ["ten-a", "ten-a"], ["ten-a", "ten-b"],
                 ["ten-b", None]]
        for mix in mixes:
            rids = [eng.submit(p + i, max_new_tokens=4, adapter=a)
                    for i, a in enumerate(mix)]
            eng.run_until_idle()
            for r in rids:
                assert len(eng.scheduler.get(r).generated) == 4
                eng.release(r)
        assert eng.decode_retraces_after_warmup == 0

    def test_tenant_billing_and_stats(self, served):
        m, store, eng, _ = served
        before = dict(eng.stats()["tenant_tokens"])
        rid = eng.submit(np.arange(3, 7, dtype=np.int32), max_new_tokens=5,
                         adapter="ten-a", tenant="acme-corp")
        _drain(eng, rid)
        st = eng.stats()
        assert (st["tenant_tokens"]["acme-corp"]
                - before.get("acme-corp", 0)) == 5
        lora = st["lora"]
        assert lora["slots"] == 4 and lora["rank"] == RANK
        assert "ten-a" in lora["resident"]


class TestAdapterStore:
    def test_unknown_adapter_typed_error(self, served):
        m, store, eng, _ = served
        with pytest.raises(AdapterLoadError, match="not registered"):
            eng.submit(np.arange(3, 7, dtype=np.int32), adapter="ghost")
        # the engine is NOT wedged: base traffic still flows
        assert len(_drain(eng, eng.submit(
            np.arange(3, 7, dtype=np.int32), max_new_tokens=2))) == 2

    def test_lru_eviction_cycles_slots(self, served, tmp_path):
        m, store, eng, _ = served
        for i in range(5):
            _mk_adapter(m, str(tmp_path / f"ev{i}.pdmodel"), f"ev{i}",
                        seed=20 + i)
            store.register(f"ev{i}", str(tmp_path / f"ev{i}.pdmodel"))
        ev0 = store.evictions
        p = np.arange(3, 7, dtype=np.int32)
        for i in range(5):             # 5 adapters through a 4-slot pool
            _drain(eng, eng.submit(p, max_new_tokens=2, adapter=f"ev{i}"))
        assert store.evictions > ev0
        snap = store.residency()
        assert len(snap["resident"]) <= 4
        assert all(r == 0 for r in snap["refs"].values())
        assert eng.decode_retraces_after_warmup == 0
        for i in range(5):
            store.unregister(f"ev{i}")

    def test_pinned_pool_exhaustion_typed_error(self, served, tmp_path):
        m, store, eng, d = served
        for i in range(3):
            _mk_adapter(m, str(tmp_path / f"pin{i}.pdmodel"), f"pin{i}",
                        seed=30 + i)
            store.register(f"pin{i}", str(tmp_path / f"pin{i}.pdmodel"))
        p = np.arange(3, 9, dtype=np.int32)
        held = [eng.submit(p, max_new_tokens=50, adapter=a)
                for a in ("ten-a", "ten-b", "pin0", "pin1")]
        try:
            with pytest.raises(AdapterLoadError, match="pool exhausted"):
                eng.submit(p, adapter="pin2")
        finally:
            for r in held:
                eng.cancel(r)
            eng.run_until_idle()
            for r in held:
                eng.release(r)
        # slots unpinned -> the refused adapter now loads fine
        assert len(_drain(eng, eng.submit(
            p, max_new_tokens=2, adapter="pin2"))) == 2
        for i in range(3):
            store.unregister(f"pin{i}")

    def test_hot_swap_under_live_traffic(self, served, tmp_path):
        """Re-registering a RESIDENT adapter rewrites its slot rows while
        a request decodes through it: the stream keeps its prefix, picks
        up the new weights mid-flight, finishes — zero retraces (pools
        are jit ARGUMENTS, so a swap changes values, never programs)."""
        m, store, eng, _ = served
        p1, p2 = (str(tmp_path / "hs1.pdmodel"), str(tmp_path / "hs2.pdmodel"))
        _mk_adapter(m, p1, "hs", seed=41)
        _mk_adapter(m, p2, "hs", seed=42, scale=0.3)
        store.register("hs", p1)
        swaps0 = store.swaps
        prompt = np.arange(3, 9, dtype=np.int32)
        rid = eng.submit(prompt, max_new_tokens=12, adapter="hs")
        eng.step()
        eng.step()
        pre = list(eng.scheduler.get(rid).generated)
        store.register("hs", p2)       # hot swap the resident slot
        post = _drain(eng, rid)
        assert len(post) == 12 and post[:len(pre)] == pre
        assert store.swaps > swaps0    # the swap was a timed slot write
        assert eng.decode_retraces_after_warmup == 0
        # a fresh request decodes through the SWAPPED weights end to end,
        # so its stream diverges from the mid-swap one
        after = _drain(eng, eng.submit(prompt, max_new_tokens=12,
                                       adapter="hs"))
        assert after != post
        store.unregister("hs")

    def test_swap_fail_chaos_typed_error(self, served, tmp_path):
        """`serving.lora.swap_fail` armed: the swap-in fails as a typed
        AdapterLoadError for the ONE request that needed it; disarmed,
        the same adapter loads fine and other traffic never noticed."""
        m, store, eng, _ = served
        path = str(tmp_path / "cz.pdmodel")
        _mk_adapter(m, path, "cz", seed=50)
        store.register("cz", path)     # registered, NOT resident
        p = np.arange(3, 7, dtype=np.int32)
        # make ten-a resident BEFORE arming, so the control request below
        # takes the already-resident fast path (no swap to fail)
        _drain(eng, eng.submit(p, max_new_tokens=1, adapter="ten-a"))
        fails0 = store.load_failures
        faults.reset()
        try:
            faults.arm("serving.lora.swap_fail", mode="always")
            with pytest.raises(AdapterLoadError, match="swap_fail"):
                eng.submit(p, adapter="cz")
            # resident adapters dodge the swap path entirely
            assert len(_drain(eng, eng.submit(
                p, max_new_tokens=2, adapter="ten-a"))) == 2
        finally:
            faults.reset()
        assert store.load_failures == fails0 + 1
        assert len(_drain(eng, eng.submit(
            p, max_new_tokens=2, adapter="cz"))) == 2
        store.unregister("cz")

    def test_store_validates_rank_and_model(self, served, tmp_path):
        m, store, eng, d = served
        paddle.seed(1)
        other = LlamaForCausalLM(_config())
        with pytest.raises(ValueError, match="different model"):
            ServingEngine(other,
                          ServingConfig(page_size=16, num_pages=8,
                                        decode_batch=1, prefill_chunk=16,
                                        max_seq_len=32),
                          adapter_store=store)
        wrong = AdapterStore(m, rank=RANK * 2, slots=2)
        with pytest.raises(ValueError, match="rank"):
            wrong.register("ten-a", str(d / "ten-a.pdmodel"))


class TestRouterTenancy:
    def test_placement_caps_and_typed_degradation(self, served):
        """Router over the warmed engine: adapter-affinity placement
        keys, a failed adapter load degrades to ONE terminal event (no
        breaker strike, no failover), and per-tenant in-flight caps
        refuse the over-cap tenant while peers sail through."""
        from paddle_tpu.serving.replica import InProcessReplica
        from paddle_tpu.serving.router import Router, RouterConfig

        m, store, eng, _ = served
        rep = InProcessReplica(eng, replica_id=0)
        try:
            router = Router([rep],
                            RouterConfig(placement="adapter",
                                         tenant_max_inflight=1),
                            start_monitor=False)
            router.monitor_tick()
            assert router.placement_key(
                {"adapter": "ten-a", "prompt_ids": [1]}) == "adapter:ten-a"

            toks, term = router.generate(
                {"prompt_ids": [3, 4, 5, 6], "max_new_tokens": 4,
                 "adapter": "ten-a", "tenant": "ten-a"})
            assert term.get("done") and len(toks) == 4

            toks, term = router.generate(
                {"prompt_ids": [3, 4, 5], "adapter": "ghost"})
            assert term["error"] == "adapter_load_failed"
            assert term["adapter"] == "ghost" and term["failovers"] == 0
            slot = router._slots[0]
            assert slot.circuit == "closed"
            assert slot.consecutive_failures == 0   # healthy replica: no strike

            g = router.stream({"prompt_ids": [3, 4, 5],
                               "max_new_tokens": 30, "tenant": "acme"})
            next(g)                                 # hold the stream open
            try:
                _, term = router.generate({"prompt_ids": [3, 4, 5],
                                           "tenant": "acme"})
                assert term["error"] == "tenant_limit"
                assert term["tenant"] == "acme"
                _, term = router.generate(
                    {"prompt_ids": [3, 4, 5], "max_new_tokens": 2,
                     "tenant": "zen"})
                assert term.get("done")             # peers unaffected
            finally:
                g.close()
            st = router.stats()
            assert st["tenant_refused"] == 1
            assert st["tenants"].get("acme", 0) == 0   # ledger drained
            assert eng.decode_retraces_after_warmup == 0
        finally:
            rep.close()


    def test_replica_kill_fails_over_adapter_traffic(self, served):
        """Failover composed with the adapter plane: two replicas, every
        payload carries an adapter and a tenant, replica 1 is killed while
        it serves. Nothing is lost, every stream equals the one the
        surviving engine gives that adapter alone (the re-prefilled request
        re-pins its adapter on the survivor's store), and the survivor's
        decode does not retrace."""
        import threading
        import time

        from paddle_tpu.serving.replica import InProcessReplica
        from paddle_tpu.serving.router import Router, RouterConfig

        m, store, eng, d = served
        paddle.seed(0)
        m2 = LlamaForCausalLM(_config())
        m2.eval()
        store2 = AdapterStore(m2, rank=RANK, slots=4)
        for aid in ("ten-a", "ten-b"):
            store2.register(aid, str(d / f"{aid}.pdmodel"))
        eng2 = ServingEngine(m2, ServingConfig(
            page_size=16, num_pages=64, decode_batch=4, prefill_chunk=16,
            max_seq_len=64), adapter_store=store2)
        _drain(eng2, eng2.submit(np.arange(3, 9, dtype=np.int32),
                                 max_new_tokens=2, adapter="ten-a"))
        rng = np.random.RandomState(4)
        n, n_new = 8, 24
        prompts = [rng.randint(1, 128, 6).astype(np.int32) for _ in range(n)]
        adapters = [("ten-a", "ten-b")[i % 2] for i in range(n)]
        want = [_drain(eng, eng.submit(p, max_new_tokens=n_new, adapter=a))
                for p, a in zip(prompts, adapters)]
        reps = [InProcessReplica(eng, replica_id=0),
                InProcessReplica(eng2, replica_id=1)]
        router = Router(reps, RouterConfig(probe_interval_s=0.05,
                                           gap_timeout_s=2.0))
        results = [None] * n

        def client(i):
            results[i] = router.generate(
                {"prompt_ids": [int(t) for t in prompts[i]],
                 "max_new_tokens": n_new, "adapter": adapters[i],
                 "tenant": adapters[i], "session": f"rc{i}"})

        def killer():
            deadline = time.time() + 5.0
            while time.time() < deadline and not eng2.scheduler.running:
                time.sleep(0.002)
            reps[1].kill()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)] + [threading.Thread(target=killer)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            assert reps[1].dead_cause is not None
            for i, res in enumerate(results):
                assert res is not None, f"request {i} got no terminal"
                toks, term = res
                assert term.get("done") is True, (i, term)
                assert toks == want[i], i
            assert eng.decode_retraces_after_warmup == 0
        finally:
            router.close()
            for rep in reps:
                rep.close()


class TestSatellites:
    def test_grouped_matmul_block_rows_provenance(self):
        """Satellite: an indivisible caller-supplied block_rows names its
        source and the FLAGS_moe_block_rows escape hatch."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

        with pytest.raises(ValueError) as ei:
            grouped_matmul(jnp.zeros((12, 4)), jnp.zeros((2, 4, 4)),
                           jnp.zeros((12,), jnp.int32), block_rows=8)
        msg = str(ei.value)
        assert "caller-supplied" in msg
        assert "FLAGS_moe_block_rows" in msg

    def test_serve_delta_backends_agree(self):
        """The TPU path (pallas grouped matmul, interpret here) and the
        CPU path (xla backend at block_rows=1 — a per-row w[gid] gather)
        produce the same delta for any unsorted slot mix. They are two
        programs that add the same float32 products in another order, so
        the deltas agree to float32 rounding (measured: 227 of 384
        elements differ, at most 3.8e-6 absolute, 5.1e-5 relative), not
        bit for bit; the trash rows are EXACTLY zero on both."""
        import jax.numpy as jnp

        from paddle_tpu.lora.seam import ServeBinding, serve_delta

        rng = np.random.default_rng(0)
        G, d, r, dout, b, t = 4, 16, RANK, 16, 8, 3
        a_pool = jnp.asarray(rng.standard_normal((G, d, r)), jnp.float32)
        b_pool = jnp.asarray(rng.standard_normal((G, r, dout)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
        slots = jnp.asarray([0, 3, 1, G, 2, 0, G, 3], jnp.int32)
        outs = [
            np.asarray(serve_delta(v, a_pool, b_pool, ServeBinding(
                {}, slots, G, block_rows=8, backend=be)))
            for be in ("pallas", "auto")]
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)
        # trash rows (gid == G) contribute an exactly-zero delta
        for out in outs:
            assert np.all(out[3] == 0) and np.all(out[6] == 0)
        assert np.any(outs[0][0] != 0)

    def test_lora_metrics_exported(self, served):
        from paddle_tpu.observability import metrics as obs_metrics

        m, store, eng, _ = served
        _drain(eng, eng.submit(np.arange(3, 7, dtype=np.int32),
                               max_new_tokens=2, adapter="ten-a",
                               tenant="ten-a"))
        text = obs_metrics.registry().prometheus_text()
        for name in ("lora_active_adapters", "lora_swap_total",
                     "lora_swap_ms", "lora_tokens_total"):
            assert name in text, f"missing metric {name}"
        assert 'tenant="ten-a"' in text
