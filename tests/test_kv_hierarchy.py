"""PR-16 KV-cache memory hierarchy, tier-1 core: quantized (int8/fp8)
page pools with in-kernel dequant (decode AND verify grids, kernel ==
reference contract), the shared observer scale codepath, the host-RAM
cold tier's allocator semantics (demotion keeps refcounts + index,
radix-hit promotion, promote_fail chaos degrades to re-prefill,
check_consistency over the host tier, 400-op aliasing fuzz with
demote/promote/evict), engine-level tier stream equality with ZERO decode
retraces across transitions, and prefix-affinity router placement."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.ops.pallas.paged_attention import (force_interpret,
                                                   paged_attention_reference,
                                                   paged_decode_attention)
from paddle_tpu.quantization import AbsmaxChannelWiseObserver, absmax_scale
from paddle_tpu.serving import (PageAllocator, ServingConfig, ServingEngine,
                                kv_page_bytes, pages_for_budget)


def _model(**over):
    paddle.seed(0)
    cfg = llama_tiny_config(**over)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


@pytest.fixture(scope="module")
def shared():
    return _model()


def _quantize(pool, qmax=127.0):
    """Host-side mirror of the model's quantize-on-write (per-slot-per-head
    absmax over the trailing head_dim axis)."""
    sc = np.maximum(np.abs(pool).max(-1) / qmax, 1e-8).astype(np.float32)
    codes = np.clip(np.round(pool / sc[..., None]), -qmax, qmax)
    return codes.astype(np.int8), sc


# ---------------------------------------------------------------------------
# quantized kernel: in-kernel dequant parity (decode + verify grids)
# ---------------------------------------------------------------------------
class TestQuantizedPagedKernel:
    def _pools(self, seed=0):
        rng = np.random.RandomState(seed)
        hkv, pages, ps, d = 2, 12, 8, 16
        k = rng.randn(hkv, pages, ps, d).astype(np.float32)
        v = rng.randn(hkv, pages, ps, d).astype(np.float32)
        pt = np.zeros((3, 4), np.int32)
        pt[0, :3] = [1, 2, 3]
        pt[1, :2] = [4, 5]
        lens = np.array([19, 9, 0], np.int32)
        return k, v, pt, lens

    @pytest.mark.parametrize("t", [None, 3], ids=["decode", "verify_frame"])
    def test_int8_kernel_matches_reference_and_bf16_within_1e2(self, t):
        """The interpret-mode Pallas kernel with fused dequant must equal
        the jnp quantized reference (same contract tier-1 runs on CPU) and
        sit within 1e-2 relative of the unquantized math."""
        k, v, pt, lens = self._pools()
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        rng = np.random.RandomState(1)
        q = (rng.randn(3, 4, 16) if t is None
             else rng.randn(3, t, 4, 16)).astype(np.float32)
        ref_bf = paged_attention_reference(q, k, v, pt, lens)
        ref_q = paged_attention_reference(q, kq, vq, pt, lens,
                                          k_scales=ks, v_scales=vs)
        with force_interpret():
            ker_q = paged_decode_attention(q, kq, vq, pt, lens,
                                           k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(ker_q), np.asarray(ref_q),
                                   atol=2e-6)
        rel = (np.abs(np.asarray(ref_q) - np.asarray(ref_bf)).max()
               / np.abs(np.asarray(ref_bf)).max())
        assert rel <= 1e-2 * 2   # per-slot absmax: ~0.4% typical
        # inactive row (len 0) still yields zeros through the quant path
        assert np.all(np.asarray(ker_q)[2] == 0)

    def test_fp8_pool_roundtrip_where_available(self):
        if not hasattr(jnp, "float8_e4m3fn"):
            pytest.skip("platform has no float8_e4m3fn")
        k, v, pt, lens = self._pools(2)
        ks = np.maximum(np.abs(k).max(-1) / 448.0, 1e-8).astype(np.float32)
        vs = np.maximum(np.abs(v).max(-1) / 448.0, 1e-8).astype(np.float32)
        kq = jnp.asarray(k / ks[..., None]).astype(jnp.float8_e4m3fn)
        vq = jnp.asarray(v / vs[..., None]).astype(jnp.float8_e4m3fn)
        q = np.random.RandomState(3).randn(3, 4, 16).astype(np.float32)
        ref_bf = paged_attention_reference(q, k, v, pt, lens)
        ref_q = paged_attention_reference(q, kq, vq, pt, lens,
                                          k_scales=ks, v_scales=vs)
        rel = (np.abs(np.asarray(ref_q) - np.asarray(ref_bf)).max()
               / np.abs(np.asarray(ref_bf)).max())
        assert rel <= 5e-2   # e4m3: 3 mantissa bits, ~6% max quant step

    def test_scale_shape_validation(self):
        k, v, pt, lens = self._pools()
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        q = np.zeros((3, 4, 16), np.float32)
        with pytest.raises(ValueError, match="scales"):
            paged_decode_attention(q, kq, vq, pt, lens, interpret=True,
                                   k_scales=ks[:, :, :4], v_scales=vs)
        with pytest.raises(ValueError, match="v_scales"):
            paged_decode_attention(q, kq, vq, pt, lens, interpret=True,
                                   k_scales=ks)


# ---------------------------------------------------------------------------
# satellite: the observer IS the KV scale codepath
# ---------------------------------------------------------------------------
class TestObserverScaleCodepath:
    def test_kv_page_scales_matches_absmax_scale(self):
        vals = jnp.asarray(np.random.RandomState(0).randn(2, 3, 4, 8),
                           jnp.float32)
        sc = AbsmaxChannelWiseObserver.kv_page_scales(vals)
        expect = absmax_scale(jnp.max(jnp.abs(vals), axis=-1), 8)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(expect))
        assert sc.shape == (2, 3, 4) and sc.dtype == jnp.float32
        # device array end to end: no host sync on the decode path
        assert isinstance(sc, jnp.ndarray)

    def test_training_observer_shares_the_same_math(self):
        """The serving KV scales and the PR-7 training observer must be
        the SAME function of absmax (one codepath, satellite 2)."""
        x = np.random.RandomState(1).randn(16, 4).astype(np.float32)
        obs = AbsmaxChannelWiseObserver(quant_bits=8)
        obs.observe(jnp.asarray(x))
        per_channel = np.asarray(obs.scale())
        expect = np.asarray(absmax_scale(jnp.max(jnp.abs(x), axis=0), 8))
        np.testing.assert_allclose(per_channel, expect)


# ---------------------------------------------------------------------------
# allocator host tier
# ---------------------------------------------------------------------------
def _toks(*vals):
    return np.asarray(vals, np.int32)


class TestHostTierAllocator:
    def test_demotion_keeps_index_and_promote_restores(self):
        a = PageAllocator(num_pages=6, page_size=2, host_pages=4)
        toks = _toks(1, 2, 3, 4)                     # 2 full pages
        assert a.ensure("A", 4)
        a.register_prefix("A", toks)
        pages = list(a.chain("A"))
        a.free_request("A")                          # -> cold, still indexed
        assert a.cold_pages == 2
        # exhaust the pool: reclaiming the cold pages demotes them
        assert a.ensure("B", 2 * a.free_pages + 2 * a.cold_pages)
        assert a.demotions == 2 and a.cold_pages == 0
        demotes, promotes = a.take_tier_ops()
        assert [p for p, _ in demotes] == pages and not promotes
        a.check_consistency()
        a.free_request("B")
        # a radix hit on the demoted prefix promotes (fresh HBM pages,
        # H2D restore queued) and the admission adopts them
        adopt, matched = a.match_prefix(toks)
        assert matched == 4 and len(adopt) == 2
        assert a.promotions == 2 and a.cold_hits == 0
        assert a.ensure("C", 5, adopt=adopt)
        assert a.cold_hits == 2                      # adopted as cold pages
        _, promotes = a.take_tier_ops()
        assert len(promotes) == 2
        assert a.host_used == 0
        a.check_consistency()

    def test_demoted_shared_page_keeps_refcounts(self):
        """A page with live sharers NEVER demotes: demotion applies only
        to refcount-0 (cold) pages, so sharers' chains are untouchable."""
        a = PageAllocator(num_pages=8, page_size=2, host_pages=4)
        toks = _toks(5, 6, 7, 8)
        assert a.ensure("A", 4)
        a.register_prefix("A", toks)
        adopt, matched = a.match_prefix(toks)
        assert a.ensure("B", 5, adopt=adopt)
        shared = a.chain("A")[:2]
        assert all(a.ref_count(p) == 2 for p in shared)
        a.free_request("A")                          # B still holds them
        assert a.cold_pages == 0                     # held, not cold
        # exhausting the pool must fail before touching B's shared pages
        assert not a.ensure("HOG", 2 * (a.free_pages + 1))
        assert all(a.ref_count(p) == 1 for p in shared)
        a.check_consistency()

    def test_cow_split_of_demoted_page_promotes_first(self):
        """CoW-split of a page that went to host: the radix hit PROMOTES
        it back into HBM at adoption, so the later make_writable split
        copies from a live HBM page (the host page is never a CoW src)."""
        a = PageAllocator(num_pages=6, page_size=2, host_pages=4)
        toks = _toks(1, 2, 3, 4)
        assert a.ensure("A", 4)
        a.register_prefix("A", toks)
        a.free_request("A")
        assert a.ensure("B", 2 * a.reclaimable_pages)   # force demotion
        assert a.demotions == 2
        a.free_request("B")
        a.take_tier_ops()
        adopt, _ = a.match_prefix(toks)
        assert a.ensure("C", 4, adopt=adopt)
        assert a.promotions == 2
        # writer touches the adopted (previously host-resident) page
        copies = a.make_writable("C", 0, 3)
        assert copies == []          # sole holder after promote: no split
        _, promotes = a.take_tier_ops()
        assert {dst for _, dst in promotes} >= set(a.chain("C")[:2])
        a.check_consistency()

    def test_promote_fail_chaos_degrades_to_reprefill(self):
        a = PageAllocator(num_pages=6, page_size=2, host_pages=4)
        toks = _toks(9, 8, 7, 6)
        assert a.ensure("A", 4)
        a.register_prefix("A", toks)
        a.free_request("A")
        assert a.ensure("B", 2 * a.reclaimable_pages)
        a.free_request("B")
        a.take_tier_ops()
        faults.reset()
        try:
            faults.arm("serving.kv.promote_fail", mode="once")
            adopt, matched = a.match_prefix(toks)
            # the failed restore degrades to a shorter (here empty) match:
            # the caller re-prefills the tail — never wedges
            assert matched == 0 and adopt == []
            assert a.promote_failures == 1
            # only the FAILED entry drops; the deeper page's entry is
            # unreachable through this prefix and FIFO-ages out later
            assert a.host_used == 1
            a.check_consistency()
            # pool still fully usable
            assert a.ensure("C", 4)
            a.check_consistency()
        finally:
            faults.reset()

    def test_host_pool_full_drops_oldest(self):
        a = PageAllocator(num_pages=12, page_size=2, host_pages=1)
        t1, t2 = _toks(1, 2), _toks(3, 4)
        assert a.ensure("A", 2)
        a.register_prefix("A", t1)
        a.free_request("A")
        assert a.ensure("B", 2)
        a.register_prefix("B", t2)
        a.free_request("B")
        assert a.cold_pages == 2
        assert a.ensure("HOG", 2 * a.reclaimable_pages)
        # one slot: the second demotion FIFO-evicts the first host entry
        assert a.demotions + a.dropped_cold >= 2 and a.host_used == 1
        a.check_consistency()

    def test_aliasing_fuzz_with_tier_transitions(self):
        """Satellite 3: the PR-12 aliasing fuzz extended with a host tier
        small enough to thrash — demote/promote/evict interleave with
        adoption, registration and CoW, check_consistency() (now covering
        the host slot partition) after EVERY op."""
        a = PageAllocator(num_pages=24, page_size=2, host_pages=6)
        rng = np.random.RandomState(16)
        live: dict[int, np.ndarray] = {}
        corpus = [rng.randint(1, 9, 12).astype(np.int32) for _ in range(4)]
        for step in range(400):
            rid = int(rng.randint(10))
            op = rng.rand()
            if rid in live and op < 0.25:
                a.free_request(rid)
                del live[rid]
            elif rid not in live:
                base = corpus[rng.randint(len(corpus))]
                n = int(rng.randint(2, base.size + 1))
                toks = base[:n].copy()
                if rng.rand() < 0.3:
                    toks[-1] = rng.randint(1, 9)
                pages, matched = a.match_prefix(toks)
                if a.ensure(rid, toks.size, adopt=pages or None):
                    live[rid] = toks
                    a.register_prefix(rid, toks)
            else:
                toks = live[rid]
                if rng.rand() < 0.5:
                    grown = np.concatenate(
                        [toks, rng.randint(1, 9, 2).astype(np.int32)])
                    if a.ensure(rid, grown.size):
                        live[rid] = grown
                else:
                    a.make_writable(rid, max(toks.size - 2, 0),
                                    toks.size - 1)
            if rng.rand() < 0.1:
                a.take_tier_ops()        # engine drains between steps
            a.check_consistency()
        assert a.demotions > 0 and a.promotions > 0   # the tier thrashed
        for rid in list(live):
            a.free_request(rid)
        a.take_tier_ops()
        a.check_consistency()


# ---------------------------------------------------------------------------
# engine level: quantized + tiered serving
# ---------------------------------------------------------------------------
class TestEngineHierarchy:
    def test_int8_capacity_and_stream_match(self, shared):
        """int8 pools admit >= 1.9x the pages at a fixed budget, and
        greedy int8 streams match bf16 per token >= 99%."""
        m, cfg = shared
        pb_model = kv_page_bytes(cfg.num_hidden_layers,
                                 cfg.num_key_value_heads, 4,
                                 cfg.hidden_size // cfg.num_attention_heads,
                                 2)
        pb_int8 = kv_page_bytes(cfg.num_hidden_layers,
                                cfg.num_key_value_heads, 4,
                                cfg.hidden_size // cfg.num_attention_heads,
                                1)
        assert pb_model / pb_int8 >= 1.9
        kw = dict(page_size=4, num_pages=64, decode_batch=4,
                  prefill_chunk=8, max_seq_len=64)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
                   for n in (7, 13, 21, 5)]
        eng_ref = ServingEngine(m, ServingConfig(**kw))
        eng_i8 = ServingEngine(m, ServingConfig(kv_cache_dtype="int8", **kw))
        assert eng_i8.kv_dtype == jnp.dtype(jnp.int8)
        assert eng_i8.kv_scale_bytes > 0
        assert eng_i8.stats()["kv_cache_dtype"] == "int8"
        out_ref = eng_ref.generate(prompts, max_new_tokens=8)
        out_i8 = eng_i8.generate(prompts, max_new_tokens=8)
        match = sum(x == y for a_, b_ in zip(out_ref, out_i8)
                    for x, y in zip(a_, b_))
        total = sum(len(s) for s in out_ref)
        assert match / total >= 0.99

    def test_int8_pages_per_byte_budget_at_7b_geometry(self):
        """A count, not a speed: at the 7B serving geometry (32 layers x
        32 KV heads x 128, pages of 16) int8 codes WITH their float32
        per-slot scales admit 1.94x the pages of bfloat16 at 4 GiB."""
        L, H, ps, D = 32, 32, 16, 128
        pb_bf16 = kv_page_bytes(L, H, ps, D, 2)
        pb_int8 = kv_page_bytes(L, H, ps, D, 1) + 2 * L * H * ps * 4
        ratio = (pages_for_budget(4 << 30, pb_int8)
                 / pages_for_budget(4 << 30, pb_bf16))
        assert 1.9 <= ratio < 2.0, ratio
        assert round(ratio, 2) == 1.94

    def test_one_byte_budget_int8_serves_the_burst_model_dtype_evicts(
            self, shared):
        """The capacity realised, structurally: at ONE byte budget a full
        decode batch exceeds the model-dtype pool, which evicts and
        re-prefills, while the int8 pool (codes + scales) holds the same
        burst with ZERO evictions; both finish every stream."""
        m, cfg = shared
        L, H = cfg.num_hidden_layers, cfg.num_key_value_heads
        D = cfg.hidden_size // cfg.num_attention_heads
        ps = 4
        pb_model = kv_page_bytes(L, H, ps, D, 4)     # float32 on the CPU
        pb_int8 = kv_page_bytes(L, H, ps, D, 1) + 2 * L * H * ps * 4
        budget = 12 * pb_model
        pages = {"model": pages_for_budget(budget, pb_model),
                 "int8": pages_for_budget(budget, pb_int8)}
        # 4 slots x (10 prompt + 12 new) tokens = 24 pages, and the null page;
        # the model-dtype pool admits three prompts and cannot grow them
        assert pages["model"] == 12 < 25 <= pages["int8"]
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, cfg.vocab_size, 10).astype(np.int32)
                   for _ in range(6)]
        evictions = {}
        for mode in ("model", "int8"):
            eng = ServingEngine(m, ServingConfig(
                page_size=ps, num_pages=pages[mode], decode_batch=4,
                prefill_chunk=8, max_seq_len=32, kv_cache_dtype=mode))
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            eng.run_until_idle()
            reqs = [eng.scheduler.get(r) for r in rids]
            assert all(len(r.generated) == 12 for r in reqs)
            evictions[mode] = sum(r.evictions for r in reqs)
            for r in rids:
                eng.release(r)
            eng.allocator.check_consistency()
        assert evictions["int8"] == 0 < evictions["model"], evictions

    def test_tier_roundtrip_stream_equality_zero_retraces(self, shared):
        """Chaos-shaped acceptance: fill the pool so a finished request's
        committed pages demote to host, then re-admit the same prompt —
        the radix hit restores via H2D and the stream is IDENTICAL, with
        zero decode retraces across every tier transition."""
        m, cfg = shared
        kw = dict(page_size=4, num_pages=12, decode_batch=2,
                  prefill_chunk=8, max_seq_len=32)
        rng = np.random.RandomState(1)
        prompt_a = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
        fillers = [rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
                   for _ in range(2)]
        eng = ServingEngine(m, ServingConfig(host_cache_mb=64, **kw))
        assert eng.host_pages > 0 and eng.allocator.tier_enabled
        first = eng.generate([prompt_a], max_new_tokens=6)[0]
        eng.mark_warmup()
        # 11 usable pages; each 18-token chain holds 5 — two fillers force
        # reclaim of A's cold pages into the host tier
        eng.generate(fillers, max_new_tokens=6)
        assert eng.allocator.demotions > 0
        assert eng.stats()["kv_host_used"] > 0
        again = eng.generate([prompt_a], max_new_tokens=6)[0]
        assert eng.allocator.promotions > 0
        assert again == first
        assert eng.decode_retraces_after_warmup == 0
        eng.allocator.check_consistency()

    def test_engine_promote_fail_reprefills_same_stream(self, shared):
        m, cfg = shared
        kw = dict(page_size=4, num_pages=12, decode_batch=2,
                  prefill_chunk=8, max_seq_len=32)
        rng = np.random.RandomState(2)
        prompt_a = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
        fillers = [rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
                   for _ in range(2)]
        eng = ServingEngine(m, ServingConfig(host_cache_mb=64, **kw))
        first = eng.generate([prompt_a], max_new_tokens=6)[0]
        eng.generate(fillers, max_new_tokens=6)
        assert eng.stats()["kv_host_used"] > 0
        faults.reset()
        try:
            faults.arm("serving.kv.promote_fail", mode="once")
            again = eng.generate([prompt_a], max_new_tokens=6)[0]
        finally:
            faults.reset()
        # the failed restore re-prefilled the whole prompt: same stream,
        # no wedge, accounting shows the degradation
        assert again == first
        assert eng.allocator.promote_failures == 1
        eng.allocator.check_consistency()

    def test_int8_with_host_tier_composes(self, shared):
        """The quantized pools and the host tier are orthogonal: scales
        demote/promote alongside their codes (one cache pytree)."""
        m, cfg = shared
        kw = dict(page_size=4, num_pages=12, decode_batch=2,
                  prefill_chunk=8, max_seq_len=32)
        rng = np.random.RandomState(3)
        prompt_a = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
        fillers = [rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
                   for _ in range(2)]
        eng = ServingEngine(m, ServingConfig(kv_cache_dtype="int8",
                                             host_cache_mb=64, **kw))
        assert set(eng._host_store) == {"k", "v", "k_scale", "v_scale"}
        first = eng.generate([prompt_a], max_new_tokens=6)[0]
        eng.generate(fillers, max_new_tokens=6)
        assert eng.allocator.demotions > 0
        again = eng.generate([prompt_a], max_new_tokens=6)[0]
        assert eng.allocator.promotions > 0
        assert again == first
        eng.allocator.check_consistency()


# ---------------------------------------------------------------------------
# router: prefix-affinity placement
# ---------------------------------------------------------------------------
class TestPrefixAffinityPlacement:
    def _router(self, placement, n=3):
        from paddle_tpu.serving.router import Router, RouterConfig

        class _Stub:
            def __init__(self, rid):
                self.replica_id = rid

            def probe(self):
                return {}

        return Router([_Stub(i) for i in range(n)],
                      RouterConfig(placement=placement, prefix_tokens=8),
                      start_monitor=False)

    def test_prefix_digest_groups_shared_prompts(self):
        r = self._router("prefix")
        try:
            shared_head = list(range(100, 108))
            p1 = {"prompt_ids": shared_head + [1, 2], "session": "u1"}
            p2 = {"prompt_ids": shared_head + [3, 4], "session": "u2"}
            p3 = {"prompt_ids": [7] * 10, "session": "u1"}
            k1, k2, k3 = (r.placement_key(p) for p in (p1, p2, p3))
            # same system prompt -> same key regardless of session/tail
            assert k1 == k2 and k1.startswith("prefix:")
            assert k3 != k1
            # promptless payloads keep session affinity as the tiebreak
            assert r.placement_key({"session": "u9"}) == "u9"
            assert r.placement_key({}) is None
            assert r.stats()["placement_mode"] == "prefix"
        finally:
            r.close()

    def test_fleet_prefix_hit_prefix_placement_beats_session(self, shared):
        """Three real engines behind the router, 4 groups of 3 requests
        sharing a 48-token prefix (12 full pages) with distinct tails, each
        group's bare prefix served once first: prefix-affinity placement
        sends a group to the replica that holds its pages (fleet prefix
        hit >= 0.9 of admitted context tokens), session placement scatters
        it (lower by more than 0.1). Counts of tokens, no clock."""
        from paddle_tpu.serving import InProcessReplica
        from paddle_tpu.serving.router import Router, RouterConfig

        m, cfg = shared
        fp, groups, per = 48, 4, 3
        rng = np.random.RandomState(23)
        prefixes = [rng.randint(1, cfg.vocab_size, fp) for _ in range(groups)]
        tails = [[rng.randint(1, cfg.vocab_size, int(rng.randint(2, 5)))
                  for _ in range(per)] for _ in range(groups)]

        def fleet_hit(placement):
            # host tier on: a finished request's prefix pages stay
            # radix-indexed, so LATER same-prefix requests can hit
            engines = [ServingEngine(m, ServingConfig(
                page_size=4, num_pages=96, decode_batch=4, prefill_chunk=16,
                max_seq_len=64, prefix_sharing=True, host_cache_mb=8))
                for _ in range(3)]
            reps = [InProcessReplica(e, replica_id=k)
                    for k, e in enumerate(engines)]
            router = Router(reps, RouterConfig(
                placement=placement, prefix_tokens=fp,
                probe_interval_s=0.05))
            try:
                for g in range(groups):
                    _, term = router.generate(
                        {"prompt_ids": [int(x) for x in prefixes[g]],
                         "max_new_tokens": 2, "session": f"seed{g}"})
                    assert term.get("done"), term
                for e in engines:
                    e.reset_stats()
                for g in range(groups):
                    for i in range(per):
                        p = np.concatenate([prefixes[g], tails[g][i]])
                        _, term = router.generate(
                            {"prompt_ids": [int(x) for x in p],
                             "max_new_tokens": 3, "session": f"s{g}-{i}"})
                        assert term.get("done"), term
                assert router.stats()["placement_mode"] == placement
            finally:
                router.close()
                for rep in reps:
                    rep.close()
            return (sum(e._prefix_matched_tokens for e in engines)
                    / sum(e._prefix_admit_tokens for e in engines))

        hit = {mode: fleet_hit(mode) for mode in ("prefix", "session")}
        assert hit["prefix"] >= 0.9, hit
        assert hit["prefix"] > hit["session"] + 0.1, hit

    def test_session_mode_preserves_pr11_behavior(self):
        r = self._router("session")
        try:
            p = {"prompt_ids": [1, 2, 3], "session": "u1"}
            assert r.placement_key(p) == "u1"
            assert r.stats()["placement_mode"] == "session"
        finally:
            r.close()

    def test_invalid_placement_rejected(self):
        from paddle_tpu.serving.router import RouterConfig
        with pytest.raises(ValueError, match="placement"):
            RouterConfig(placement="sticky").resolved()

    def test_prefix_tokens_bound_the_digest(self):
        """Tokens past prefix_tokens must NOT split the placement group —
        the digest is bounded so one long shared preamble maps every
        continuation to one replica."""
        r = self._router("prefix")
        try:
            head = list(range(8))
            a = {"prompt_ids": head + [50] * 20}
            b = {"prompt_ids": head + [60] * 5}
            assert r.placement_key(a) == r.placement_key(b)
        finally:
            r.close()
