"""Serving engine, tier-1 core: KV page allocator invariants (exhaustion
-> eviction order, chain free, aliasing, mid-decode cancel), sampling,
decode parity vs the full-sequence forward THROUGH the interpret-mode
Pallas paged kernel (incl. GQA/bf16 <= 1e-3), the zero-retrace contract,
and the serving-package pickle grep guard. System-level scheduling + HTTP
coverage lives in test_serving_system.py (slow tier — each extra engine
costs a fresh XLA compile, and tier-1 runs near its wall-clock budget)."""
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingScheduler, PageAllocator,
                                Request, RequestState, ServingConfig,
                                ServingEngine, kv_page_bytes,
                                pages_for_budget, sample_tokens)


def _model(**over):
    paddle.seed(0)
    cfg = llama_tiny_config(**over)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, **over):
    kw = dict(page_size=4, num_pages=64, decode_batch=4, prefill_chunk=8,
              max_seq_len=64)
    kw.update(over)
    return ServingEngine(m, ServingConfig(**kw))


def _prompts(rng, cfg, lens):
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ONE shared model + engine for the engine-level tests: every ServingEngine
# owns its own jit closures, so each extra engine costs a fresh decode +
# prefill compile (~4 s on the CI box). Tests must leave the engine idle
# (generate() and cancel() free all pages).
@pytest.fixture(scope="module")
def shared():
    m, cfg = _model()
    return m, cfg, _engine(m)


_teacher_fwd_cache = {}


def _teacher_greedy(m, prompt, n, pad=64):
    """Greedy continuation via the FULL-sequence forward, jitted ONCE on a
    padded frame (causal attention: tail padding can't affect the logits
    at the last real position) — an eager per-token loop would dominate
    the suite's wall clock."""
    from paddle_tpu.parallel.train_step import functional_call

    if id(m) not in _teacher_fwd_cache:
        params = [p._value for p in m.parameters()]

        def fwd(params, ids):
            out = functional_call(m, params, (ids,), training=False)
            return out._value

        _teacher_fwd_cache[id(m)] = (jax.jit(fwd), params)
    fn, params = _teacher_fwd_cache[id(m)]
    seq = [int(t) for t in np.asarray(prompt)]
    for _ in range(n):
        ids = np.zeros((1, pad), np.int64)
        ids[0, :len(seq)] = seq
        lg = np.asarray(fn(params, jnp.asarray(ids)), np.float32)
        seq.append(int(np.argmax(lg[0, len(seq) - 1])))
    return seq[len(prompt):]


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

class TestPageAllocator:
    def test_all_or_nothing_and_chain_free(self):
        a = PageAllocator(num_pages=6, page_size=4)      # 5 usable
        assert a.ensure("r0", 9)                          # 3 pages
        assert a.free_pages == 2
        assert not a.ensure("r1", 12)                     # needs 3 > 2 free
        assert a.free_pages == 2 and a.chain("r1") == []  # nothing leaked
        assert a.ensure("r1", 8)                          # 2 pages fits
        a.check_consistency()
        assert a.free_request("r0") == 3
        assert a.free_pages == 3
        a.check_consistency()

    def test_no_aliasing_across_concurrent_requests(self):
        a = PageAllocator(num_pages=32, page_size=2)
        rng = np.random.RandomState(0)
        live = {}
        for step in range(200):
            rid = rng.randint(8)
            if rid in live and rng.rand() < 0.3:
                a.free_request(rid)
                del live[rid]
            else:
                tokens = live.get(rid, 0) + rng.randint(1, 5)
                if a.ensure(rid, tokens):
                    live[rid] = tokens
            a.check_consistency()
        rows = [a.page_table_row(r, 16) for r in live]
        used = [p for row in rows for p in row if p != 0]
        assert len(used) == len(set(used))               # no shared pages

    def test_null_page_never_allocated(self):
        a = PageAllocator(num_pages=4, page_size=1)
        a.ensure("r", 3)                                  # the whole pool
        assert 0 not in a.chain("r")
        row = a.page_table_row("r", 8)
        assert row[3:].tolist() == [0] * 5                # null-padded

    def test_budget_accounting(self):
        pb = kv_page_bytes(num_layers=2, num_kv_heads=2, page_size=16,
                           head_dim=64, dtype_bytes=2)
        assert pb == 2 * 2 * 2 * 16 * 64 * 2      # k+v * L * H * ps * D * b
        assert pages_for_budget(10 * pb, pb) == 10
        # PR-16 hardening: budgets that cannot back a working pool fail
        # LOUDLY at sizing time, not later inside the engine
        with pytest.raises(ValueError, match="positive"):
            pages_for_budget(0, pb)
        with pytest.raises(ValueError, match="positive"):
            pages_for_budget(-1, pb)
        with pytest.raises(ValueError, match=">= 2"):
            pages_for_budget(pb, pb)                      # 1 page < null + 1
        with pytest.raises(ValueError, match="page_bytes"):
            pages_for_budget(10 * pb, 0)


class TestSchedulerEviction:
    def _sched(self, num_pages, batch=4, smax=64):
        a = PageAllocator(num_pages=num_pages, page_size=4)
        return ContinuousBatchingScheduler(a, batch, smax), a

    def test_exhaustion_evicts_youngest_first(self):
        sched, a = self._sched(num_pages=6)               # 5 usable
        reqs = [Request(prompt=np.arange(1, 8, dtype=np.int32),
                        max_new_tokens=30) for _ in range(3)]
        for r in reqs:
            sched.submit(r)
        admitted = sched.admissions()                     # 2 pages each
        assert [r.rid for r in admitted] == [reqs[0].rid, reqs[1].rid]
        for r in admitted:
            sched.activate(r)
        # exhaust: age both requests to 13 tokens (4 pages each, 8 > 5)
        for r in admitted:
            r.generated.extend([1] * 6)
        evicted = sched.grow()
        # the YOUNGEST (last-admitted) is preempted, copy-free
        assert evicted == [reqs[1]]
        assert reqs[1].state is RequestState.WAITING
        assert reqs[1].evictions == 1
        assert a.chain(reqs[1].rid) == []                 # pages returned
        assert sched.waiting[0] is reqs[1]                # front of queue
        a.check_consistency()

    def test_mid_decode_cancel_frees_chain(self):
        sched, a = self._sched(num_pages=16)
        r = Request(prompt=np.arange(1, 9, dtype=np.int32))
        sched.submit(r)
        for q in sched.admissions():
            sched.activate(q)
        assert a.used_pages > 0
        assert sched.cancel(r.rid)
        assert r.state is RequestState.CANCELLED
        assert a.used_pages == 0
        assert not sched.running
        assert not sched.cancel(r.rid)                    # idempotent
        a.check_consistency()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def _logits(self, rng, b=4, v=64):
        return jnp.asarray(rng.randn(b, v).astype(np.float32) * 3)

    def _keys(self, b, seed=0):
        return jnp.asarray(
            np.stack([np.asarray(jax.random.PRNGKey(seed + i))
                      for i in range(b)]).astype(np.uint32))

    def test_greedy_is_argmax_and_key_advances(self):
        rng = np.random.RandomState(0)
        lg = self._logits(rng)
        keys = self._keys(4)
        toks, nk = sample_tokens(lg, keys, jnp.zeros(4),
                                 jnp.zeros(4, jnp.int32), jnp.ones(4))
        assert toks.tolist() == np.argmax(np.asarray(lg), -1).tolist()
        assert not np.array_equal(np.asarray(nk), np.asarray(keys))

    def test_top_k_bounds_support(self):
        rng = np.random.RandomState(1)
        lg = self._logits(rng, b=1)
        top5 = set(np.argsort(-np.asarray(lg)[0])[:5].tolist())
        for i in range(12):
            toks, _ = sample_tokens(lg, self._keys(1, seed=i),
                                    jnp.ones(1), jnp.full((1,), 5, jnp.int32),
                                    jnp.ones(1))
            assert int(toks[0]) in top5

    def test_top_p_tiny_is_argmax(self):
        rng = np.random.RandomState(2)
        lg = self._logits(rng, b=2)
        toks, _ = sample_tokens(lg, self._keys(2), jnp.ones(2),
                                jnp.zeros(2, jnp.int32),
                                jnp.full((2,), 1e-6))
        assert toks.tolist() == np.argmax(np.asarray(lg), -1).tolist()

    def test_rows_independent(self):
        """A request's stream depends only on its own key: changing a
        batch-mate's params/logits leaves row 0 unchanged."""
        rng = np.random.RandomState(3)
        lg = self._logits(rng, b=2)
        keys = self._keys(2)
        t1, _ = sample_tokens(lg, keys, jnp.ones(2), jnp.zeros(2, jnp.int32),
                              jnp.ones(2))
        lg2 = lg.at[1].set(-lg[1])
        t2, _ = sample_tokens(lg2, keys, jnp.asarray([1.0, 0.3]),
                              jnp.asarray([0, 7], jnp.int32),
                              jnp.asarray([1.0, 0.5]))
        assert int(t1[0]) == int(t2[0])


# ---------------------------------------------------------------------------
# decode parity through the model (the Pallas kernel under interpret)
# ---------------------------------------------------------------------------

class TestDecodeParity:
    def _roundtrip(self, m, cfg, prompt, n_decode, atol):
        """Prefill + incremental decode vs the full-sequence forward (which
        runs flash/XLA attention): per-token logits must agree."""
        from paddle_tpu.parallel.train_step import functional_call

        L = cfg.num_hidden_layers
        hkv = cfg.num_key_value_heads
        d = cfg.hidden_size // cfg.num_attention_heads
        ps, pmax = 4, 6          # small page grid: interpret mode runs it
        dtype = m.parameters()[0]._value.dtype
        ck = jnp.zeros((L, hkv, 24, ps, d), dtype)
        cv = jnp.zeros_like(ck)
        params = [p._value for p in m.parameters()]
        seq = np.asarray(prompt, np.int32)
        full = np.asarray(
            m(paddle.to_tensor(seq[None].astype(np.int64)))._value,
            np.float32)[0]
        lp = seq.size - n_decode
        pt = np.zeros((1, pmax), np.int32)
        npages = -(-seq.size // ps)
        pt[0, :npages] = np.arange(1, npages + 1)
        cpad = 16
        ids = np.zeros((1, cpad), np.int32)
        ids[0, :lp] = seq[:lp]
        logits, cache = functional_call(
            m, params, (paddle.to_tensor(ids.astype(np.int64)),),
            dict(cache={"k": ck, "v": cv}, page_table=jnp.asarray(pt),
                 context_lens=jnp.asarray([lp], np.int32),
                 position_ids=jnp.asarray(np.arange(cpad)[None], np.int32),
                 ctx_pad=16), training=False, method="decode_forward")
        np.testing.assert_allclose(
            np.asarray(logits._value, np.float32)[0, :lp], full[:lp],
            atol=atol, rtol=atol)
        for i in range(n_decode):
            lens = lp + i
            out = functional_call(
                m, params,
                (paddle.to_tensor(np.asarray([[seq[lens - 1]]], np.int64)),),
                dict(cache=cache, page_table=jnp.asarray(pt),
                     context_lens=jnp.asarray([lens], np.int32),
                     position_ids=jnp.asarray([[lens - 1]], np.int32)),
                training=False, method="decode_forward")
            lg, cache = out
            np.testing.assert_allclose(
                np.asarray(lg._value, np.float32)[0, 0], full[lens - 1],
                atol=atol, rtol=atol)

    def test_fp32_parity(self, paged_interpret, flash_interpret):
        m, cfg = _model(num_key_value_heads=4)
        rng = np.random.RandomState(0)
        self._roundtrip(m, cfg, rng.randint(1, cfg.vocab_size, 10),
                        n_decode=2, atol=2e-4)

    def test_bf16_gqa_parity_1e3(self, paged_interpret, flash_interpret):
        """ISSUE acceptance: paged decode (interpret kernel) vs full-
        sequence flash attention, per-token logits <= 1e-3 in bf16, GQA."""
        m, cfg = _model(num_key_value_heads=2)
        m.to(dtype="bfloat16")
        rng = np.random.RandomState(1)
        self._roundtrip(m, cfg, rng.randint(1, cfg.vocab_size, 11),
                        n_decode=3, atol=1e-3)


# ---------------------------------------------------------------------------
# engine (the shared-engine fast core; system tests in test_serving_system)
# ---------------------------------------------------------------------------

class TestEngine:
    def test_greedy_parity_vs_full_forward(self, shared):
        m, cfg, eng = shared
        rng = np.random.RandomState(0)
        prompts = _prompts(rng, cfg, (5, 11, 17))
        outs = eng.generate(prompts, max_new_tokens=4)
        for p, got in zip(prompts, outs):
            assert got == _teacher_greedy(m, p, 4)

    def test_zero_decode_retraces_after_warmup(self, shared):
        m, cfg, eng = shared
        rng = np.random.RandomState(2)
        eng.generate(_prompts(rng, cfg, (5,)), max_new_tokens=2)
        eng.mark_warmup()
        # different lengths, sampling params, batch mixes — one program
        eng.generate(_prompts(rng, cfg, (9, 3, 14)), max_new_tokens=4,
                     temperature=0.7, top_k=9, top_p=0.8)
        eng.generate(_prompts(rng, cfg, (21,)), max_new_tokens=3)
        assert eng.decode_retraces_after_warmup == 0

    def test_mid_decode_cancel_frees_pages_engine(self, shared):
        m, cfg, eng = shared
        rng = np.random.RandomState(4)
        rid = eng.submit(rng.randint(1, cfg.vocab_size, 9).astype(np.int32),
                         max_new_tokens=50)
        for _ in range(3):
            eng.step()
        assert len(eng.scheduler.get(rid).generated) == 3
        assert eng.allocator.used_pages > 0
        assert eng.cancel(rid)
        assert eng.allocator.used_pages == 0
        assert not eng.step()                       # idle again
        eng.allocator.check_consistency()

    def test_pool_too_small_raises(self, shared):
        m, cfg, _ = shared
        with pytest.raises(ValueError, match="cannot hold ONE"):
            _engine(m, num_pages=4, max_seq_len=64)
        eng = _engine(m, num_pages=18, max_seq_len=64)
        with pytest.raises(ValueError, match="serving_max_seq_len"):
            eng.submit(np.arange(1, 60, dtype=np.int32), max_new_tokens=8)

    def test_rope_limit_guard(self, shared):
        m, cfg, _ = shared                          # max_pos 128
        with pytest.raises(ValueError, match="rope_max_position"):
            _engine(m, max_seq_len=256)
        m2, _ = _model(rope_max_position=256)
        eng = _engine(m2, max_seq_len=256, num_pages=128)
        assert eng.pages_per_seq == 64              # construction only

    def test_donated_params_raise_at_construction(self):
        """Serving a just-trained model whose params were donated into a
        CompiledTrainStep program must fail with the sync_params_to_model
        pointer, not an opaque deleted-array error mid-prefill."""
        m, cfg = _model()
        m.parameters()[0]._value.delete()
        with pytest.raises(ValueError, match="sync_params_to_model"):
            _engine(m)

    def test_forward_past_rope_table_raises(self):
        m, cfg = _model(max_position_embeddings=16)
        ids = paddle.to_tensor(np.ones((1, 32), np.int64))
        with pytest.raises(ValueError, match="rope_max_position"):
            m(ids)

    def test_generate_timeout_cancels_request(self, shared):
        """A /generate past its deadline emits a timeout event, frees the
        request's pages, and releases its bookkeeping (no driver thread ->
        no tokens ever land)."""
        m, cfg, eng = shared
        events = list(eng._http_generate(
            {"prompt_ids": [5, 6, 7], "max_new_tokens": 8},
            deadline=time.monotonic() - 1.0))
        assert events[-1]["error"] == "timeout"
        rid = events[-1]["rid"]
        assert eng.allocator.used_pages == 0
        assert rid not in eng.scheduler._by_rid     # released, not leaked
        assert rid not in eng._keys

    def test_client_disconnect_cancels_request(self, shared):
        """Closing a /generate stream mid-flight (GeneratorExit) must free
        the abandoned request's slot and pages immediately."""
        m, cfg, eng = shared
        import threading

        gen = eng._http_generate({"prompt_ids": [5, 6, 7],
                                  "max_new_tokens": 50},
                                 deadline=time.monotonic() + 60)
        stop = threading.Event()

        def drive():                   # the generator submits on first
            while not stop.is_set():   # next(); steps must come from a
                with eng._http_lock:   # second thread, as in serve_http
                    if not eng.scheduler.idle:
                        eng.step()
                time.sleep(0.002)

        t = threading.Thread(target=drive, daemon=True)
        t.start()
        try:
            first = next(gen)
        finally:
            stop.set()
            t.join(timeout=10)
        assert "token" in first
        rid = first["rid"]
        gen.close()                                 # client went away
        assert eng.allocator.used_pages == 0
        assert rid not in eng.scheduler._by_rid
        assert not eng.scheduler.running


# ---------------------------------------------------------------------------
# CI guard
# ---------------------------------------------------------------------------

class TestNoPickle:
    def test_serving_package_never_imports_pickle(self):
        """Tier-1 grep guard (the elastic-checkpoint precedent): the
        serving stack — package + paged kernel — must stay pickle-free."""
        import paddle_tpu.ops.pallas.paged_attention as paged
        import paddle_tpu.serving as pkg

        files = [paged.__file__]
        root = os.path.dirname(pkg.__file__)
        files += [os.path.join(root, n) for n in os.listdir(root)
                  if n.endswith(".py")]
        offenders = []
        for path in files:
            with open(path) as f:
                src = f.read()
            for needle in ("pickle.load", "pickle.dump", "import pickle",
                           "cPickle"):
                if needle in src:
                    offenders.append(f"{os.path.basename(path)}: {needle}")
        assert not offenders, offenders


# ---------------------------------------------------------------------------
# PR 12: copy-on-write shared-prefix pages (allocator level)
# ---------------------------------------------------------------------------
class TestPrefixSharing:
    def test_kv_page_bytes_takes_cache_dtype(self):
        """Satellite regression: page sizing follows the CACHE dtype, not
        the compute dtype — an int8 KV pool halves page bytes vs bf16 (so
        a budget buys 2x the pages), and the legacy itemsize-int spelling
        keeps working."""
        bf16 = kv_page_bytes(2, 2, 16, 64, dtype_bytes=jnp.bfloat16)
        int8 = kv_page_bytes(2, 2, 16, 64, dtype_bytes=jnp.int8)
        assert bf16 == 2 * int8 == kv_page_bytes(2, 2, 16, 64, 2)
        assert kv_page_bytes(2, 2, 16, 64, np.float32) == 2 * bf16
        assert pages_for_budget(10 * int8, int8) == 2 * pages_for_budget(
            10 * int8, bf16)

    def test_match_adopt_refcount(self):
        a = PageAllocator(num_pages=16, page_size=4)
        toks = np.arange(100, 111, dtype=np.int32)       # 11 tokens
        assert a.ensure("a", toks.size)
        assert a.register_prefix("a", toks) == 2         # 2 FULL pages only
        pages, matched = a.match_prefix(toks)
        assert matched == 8 and pages == a.chain("a")[:2]
        # a diverging prefix matches only the common full pages
        other = toks.copy(); other[5] += 1
        _, m2 = a.match_prefix(other)
        assert m2 == 4
        assert a.ensure("b", 10, adopt=pages)
        assert a.chain("b")[:2] == pages
        assert all(a.ref_count(p) == 2 for p in pages)
        assert a.ref_count(a.chain("b")[2]) == 1
        a.check_consistency()
        # sharers keep the pages when one holder frees
        a.free_request("a")
        assert all(a.ref_count(p) == 1 for p in pages)
        _, m3 = a.match_prefix(toks)
        assert m3 == 8                                   # still indexed
        a.check_consistency()
        a.free_request("b")
        assert a.free_pages == a.num_pages - 1           # no leak
        assert a.match_prefix(toks) == ([], 0)           # index emptied
        a.check_consistency()

    def test_adoption_all_or_nothing_on_exhaustion(self):
        a = PageAllocator(num_pages=6, page_size=4)      # 5 usable
        toks = np.arange(1, 9, dtype=np.int32)
        assert a.ensure("a", 8)
        a.register_prefix("a", toks)
        pages, _ = a.match_prefix(toks)
        assert a.ensure("x", 4)                          # 1 page
        assert a.ensure("y", 8)                          # 2 pages -> 0 free
        # adopting 2 shared + needing 2 fresh must fail atomically
        assert not a.ensure("b", 16, adopt=pages)
        assert a.chain("b") == []
        assert all(a.ref_count(p) == 1 for p in pages)
        a.check_consistency()

    def test_cow_swaps_writer_only(self):
        a = PageAllocator(num_pages=16, page_size=4)
        toks = np.arange(1, 9, dtype=np.int32)
        assert a.ensure("a", 8)
        a.register_prefix("a", toks)
        pages, _ = a.match_prefix(toks)
        assert a.ensure("b", 9, adopt=pages)             # shares 2, owns 1
        before_a = a.chain("a")
        copies = a.make_writable("b", 7, 8)              # page idx 1..2
        assert len(copies) == 1                          # only idx 1 shared
        (src, dst), = copies
        assert src == before_a[1] and a.chain("b")[1] == dst
        assert a.chain("a") == before_a                  # sharer untouched
        assert a.ref_count(src) == 1 and a.ref_count(dst) == 1
        assert a.cow_copies == 1
        # the index entry stays with the ORIGINAL page
        p2, m2 = a.match_prefix(toks)
        assert m2 == 8 and p2 == before_a[:2]
        a.check_consistency()
        # exhaustion: all-or-nothing None, nothing changed
        for i in range(a.free_pages):
            assert a.ensure(f"f{i}", 4)
        assert a.ensure("c", 8, adopt=a.match_prefix(toks)[0])
        assert a.make_writable("c", 0, 7) is None
        a.check_consistency()

    def test_aliasing_fuzz_with_shared_cow_chains(self):
        """ISSUE acceptance: the PR-9 aliasing fuzz extended with prefix
        adoption, registration and copy-on-write — check_consistency()
        (refcounts == holding chains, free/live partition, index points
        at live pages) must hold after EVERY op, and a full teardown
        leaves zero allocated pages."""
        a = PageAllocator(num_pages=48, page_size=2)
        rng = np.random.RandomState(7)
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
                    toks[-1] = rng.randint(1, 9)         # diverge the tail
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
            a.check_consistency()
        for rid in list(live):
            a.free_request(rid)
        a.check_consistency()
        assert a.free_pages == a.num_pages - 1           # no page leaked


class TestSharedChainEviction:
    def test_evict_shared_chain_requeues_without_freeing_sharers(self):
        """Satellite: evicting a request whose chain holds SHARED pages
        re-queues it (front, WAITING) while every sharer keeps its pages
        — only the victim's exclusive refs return to the free list."""
        a = PageAllocator(num_pages=10, page_size=4)     # 9 usable
        s = ContinuousBatchingScheduler(a, max_batch=4, max_seq_len=64,
                                        prefix_sharing=True)
        toks = np.arange(1, 9, dtype=np.int32)           # 2 full pages
        holder = Request(prompt=toks, max_new_tokens=4)
        victim = Request(prompt=toks, max_new_tokens=4)
        s.submit(holder); s.submit(victim)
        admitted = s.admissions(limit=1)
        assert admitted == [holder]
        a.register_prefix(holder.rid, toks)              # engine's step
        s.activate(holder)
        admitted = s.admissions(limit=1)
        assert admitted == [victim] and victim.matched_tokens == 8
        s.activate(victim)
        shared = a.chain(holder.rid)[:2]
        assert a.chain(victim.rid)[:2] == shared
        free_before = a.free_pages
        # exhaust the pool so grow() must evict the YOUNGEST (the sharer)
        assert a.ensure("hog", 4 * free_before)
        holder.generated = [1]                           # forces growth
        evicted = s.grow()
        assert evicted == [victim]
        assert victim.state == RequestState.WAITING
        assert s.waiting[0] is victim and victim.matched_tokens == 0
        # the sharers' pages survived the eviction
        assert a.chain(holder.rid)[:2] == shared
        assert all(a.ref_count(p) == 1 for p in shared)
        a.check_consistency()


# ---------------------------------------------------------------------------
# PR 12: speculative decoding (engine level; reference decode path)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def spec_shared(shared):
    """ONE speculative engine (K=3, sharing on) over the module's shared
    model — extra verify windows compile on demand and are cached per K."""
    m, cfg, _ = shared
    return m, cfg, _engine(m, spec_k=3, prefix_sharing=True)


def _aligned(*engines, seq=1000):
    """Pin the per-engine submission counters so PRNG key streams match
    across engines (keys are keyed by submission ORDER)."""
    for e in engines:
        e._submit_seq = seq


class TestSpeculativeDecoding:
    def test_greedy_stream_bit_equal_and_multi_token_steps(self, shared,
                                                           spec_shared):
        """ISSUE acceptance: greedy streams with speculation + prefix
        sharing ON are bit-equal to the PR-9 plain-decode engine, while
        committing > 1 token per dispatch."""
        m, cfg, base = shared
        _, _, spec = spec_shared
        rng = np.random.RandomState(11)
        sysp = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
        prompts = [np.concatenate([sysp, t]) for t in
                   _prompts(rng, cfg, (3, 6, 5))]
        ref = base.generate(prompts, max_new_tokens=12)
        spec.reset_stats()
        out = spec.generate(prompts, max_new_tokens=12)
        assert out == ref
        assert spec.accepted_tokens_per_step > 1.0
        assert spec.prefix_hit_rate > 0.0                # sysp pages shared
        spec.allocator.check_consistency()
        assert spec.allocator.free_pages == spec.allocator.num_pages - 1

    def test_temperature_stream_bit_equal(self, shared, spec_shared):
        """Sampled (temp/top-k/top-p) streams are bit-equal too: the
        verify frame draws position i with the KEY plain decode would
        hold after i commits, and acceptance == sampled-token equality."""
        m, cfg, base = shared
        _, _, spec = spec_shared
        rng = np.random.RandomState(12)
        prompts = _prompts(rng, cfg, (5, 9, 7))
        _aligned(base, spec)
        ref = base.generate(prompts, max_new_tokens=10, temperature=0.8,
                            top_k=24, top_p=0.9)
        out = spec.generate(prompts, max_new_tokens=10, temperature=0.8,
                            top_k=24, top_p=0.9)
        assert out == ref

    def test_k1_degenerate_matches_plain_decode(self, shared, spec_shared):
        """ISSUE acceptance: K=1 (one draft + bonus) reproduces the PR-9
        stream exactly and never over-commits past the budget."""
        m, cfg, base = shared
        _, _, spec = spec_shared
        rng = np.random.RandomState(13)
        prompts = _prompts(rng, cfg, (4, 8))
        ref = base.generate(prompts, max_new_tokens=9)
        spec.configure_speculation(spec_k=1)
        try:
            out = spec.generate(prompts, max_new_tokens=9)
        finally:
            spec.configure_speculation(spec_k=3)
        assert out == ref
        assert all(len(o) == 9 for o in out)

    def test_zero_retraces_across_k(self, shared, spec_shared):
        """ISSUE acceptance: after each verify window compiles once,
        stepping ANY warmed K (and toggling between them) never
        retraces — per-request windows ride the signature as arrays."""
        m, cfg, spec = spec_shared
        rng = np.random.RandomState(14)
        for k in (2, 3):                                 # warm both
            spec.configure_speculation(spec_k=k)
            spec.generate(_prompts(rng, cfg, (5,)), max_new_tokens=6)
        spec.mark_warmup()
        for k in (3, 2, 3):
            spec.configure_speculation(spec_k=k)
            spec.generate(_prompts(rng, cfg, (6, 4)), max_new_tokens=8,
                          temperature=0.7)
        assert spec.decode_retraces_after_warmup == 0
        spec.configure_speculation(spec_k=3)

    def test_toggle_spec_on_mid_flight_reseeds_proposer(self, shared,
                                                        spec_shared):
        """Turning speculation ON while requests are live must reseed the
        proposer from each committed stream (plain decode neither seeds
        nor feeds it): the continued stream stays bit-equal and the live
        request drafts from real tables, not missing state."""
        m, cfg, base = shared
        _, _, spec = spec_shared
        rng = np.random.RandomState(15)
        prompt = _prompts(rng, cfg, (7,))[0]
        ref = base.generate([prompt], max_new_tokens=12)[0]
        spec.configure_speculation(spec_k=0)
        try:
            rid = spec.submit(prompt, max_new_tokens=12)
            for _ in range(4):                   # plain-decode opening
                spec.step()
            assert rid not in spec._proposer._state
            spec.configure_speculation(spec_k=3)
            assert rid in spec._proposer._state  # reseeded mid-flight
            spec.run_until_idle()
        finally:
            spec.configure_speculation(spec_k=3)
        out = list(spec.scheduler.get(rid).generated)
        spec.release(rid)
        assert out == ref
        spec.allocator.check_consistency()

    def test_cow_write_leaves_sharer_bytes_identical(self, shared):
        """ISSUE acceptance: a full-prefix admission adopts every page;
        its first decode rewrite triggers copy-on-write, and the
        sharer's pages are BYTE-identical afterwards."""
        m, cfg, _ = shared
        eng = _engine(m, spec_k=2, prefix_sharing=True)
        rng = np.random.RandomState(15)
        prompt = rng.randint(1, cfg.vocab_size, 16).astype(np.int32)
        # A outlives B (large budget) so its chain still holds the shared
        # pages while B copy-on-writes
        ra = eng.submit(prompt, max_new_tokens=40)
        eng.step()                                       # admit+prefill A
        a_pages = eng.allocator.chain(ra)[:4]
        ck_before = np.asarray(eng._ck[:, :, a_pages])
        cv_before = np.asarray(eng._cv[:, :, a_pages])
        rb = eng.submit(prompt, max_new_tokens=8)        # full 4-page match
        req_b = eng.scheduler.get(rb)
        while not req_b.finished:
            eng.step()
        assert req_b.matched_tokens == 16                # prefill skipped
        assert eng.allocator.cow_copies >= 1
        assert eng.allocator.chain(ra)[:4] == a_pages    # A untouched
        np.testing.assert_array_equal(
            np.asarray(eng._ck[:, :, a_pages]), ck_before)
        np.testing.assert_array_equal(
            np.asarray(eng._cv[:, :, a_pages]), cv_before)
        eng.cancel(ra)
        eng.allocator.check_consistency()
        # B's stream equals A's prefix (same prompt, greedy; A had the
        # larger budget so it is the longer stream)
        req_a = eng.scheduler.get(ra)
        assert req_a.generated[:len(req_b.generated)] == req_b.generated

    def test_verify_mismatch_chaos_degrades_to_plain_decode(self, shared,
                                                            spec_shared):
        """Satellite: the serving.spec.verify_mismatch fault point forces
        FULL rejection every step — the engine must degrade to one
        committed token per dispatch with the exact same stream, not
        wedge."""
        from paddle_tpu.distributed.resilience import faults

        m, cfg, base = shared
        _, _, spec = spec_shared
        rng = np.random.RandomState(16)
        prompts = _prompts(rng, cfg, (5, 7))
        ref = base.generate(prompts, max_new_tokens=8)
        spec.reset_stats()
        faults.arm("serving.spec.verify_mismatch", mode="always")
        try:
            out = spec.generate(prompts, max_new_tokens=8)
        finally:
            faults.disarm("serving.spec.verify_mismatch")
        assert out == ref
        assert faults.fired("serving.spec.verify_mismatch") > 0
        assert spec.accepted_tokens_per_step == 1.0      # plain decode rate

    def test_prefix_skip_prefill_and_stats(self, shared):
        """A second same-prompt admission adopts the registered pages:
        prefill runs zero tail chunks, the hit rate reflects it, and
        stats() carries the PR-12 fields the router consumes."""
        m, cfg, _ = shared
        eng = _engine(m, spec_k=0, prefix_sharing=True)
        rng = np.random.RandomState(17)
        prompt = rng.randint(1, cfg.vocab_size, 16).astype(np.int32)
        eng.generate([prompt], max_new_tokens=4)
        # second request arrives while nothing shares -> index emptied on
        # release, so submit BOTH to overlap
        eng.reset_stats()
        o = eng.generate([prompt, prompt], max_new_tokens=4)
        assert o[0] == o[1]
        assert eng.prefix_hit_rate >= 0.4                # 16 of 32+ tokens
        st = eng.stats()
        for key in ("accepted_tokens_per_step", "prefix_hit_rate",
                    "cow_copies", "spec_k", "draft_ms_total"):
            assert key in st
        eng.allocator.check_consistency()


class TestSpeculativeInterpretKernel:
    """ISSUE acceptance: speculative streams bit-equal to plain decode ON
    THE INTERPRET KERNEL PATH (the exact TPU decode/verify kernel — the
    paged_interpret fixture pins it; prefill keeps the engine's normal
    dispatch), fp32 + bf16 GQA. Small engines bound the interpret grid."""

    def _run(self, dtype, kv_heads, paged_on):
        m, cfg = _model(num_key_value_heads=kv_heads)
        if dtype == "bfloat16":
            m.to(dtype="bfloat16")
        kw = dict(page_size=4, num_pages=24, decode_batch=2,
                  prefill_chunk=8, max_seq_len=16)
        rng = np.random.RandomState(21)
        prompts = _prompts(rng, cfg, (5, 7))
        base = ServingEngine(m, ServingConfig(**kw, spec_k=0,
                                              prefix_sharing=False))
        spec = ServingEngine(m, ServingConfig(**kw, spec_k=2,
                                              prefix_sharing=True))
        _aligned(base, spec)
        ref = base.generate(prompts, max_new_tokens=5, temperature=0.5,
                            top_k=16)
        out = spec.generate(prompts, max_new_tokens=5, temperature=0.5,
                            top_k=16)
        assert out == ref
        assert spec.decode_traces >= 1

    def test_fp32(self, paged_interpret):
        self._run("float32", 4, True)

    @pytest.mark.slow
    def test_bf16_gqa(self, paged_interpret):
        self._run("bfloat16", 2, True)
