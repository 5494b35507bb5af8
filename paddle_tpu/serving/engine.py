"""The serving engine: paged KV cache + continuous-batching decode, with
speculative self-draft decoding and copy-on-write prefix page sharing.

Compiled-signature strategy (ZERO decode retraces):

  * ONE decode program per draft window K. Every decode step runs the
    fixed ``[serving_decode_batch]`` slot layout — token ids, context
    lens, page tables, PRNG keys, per-request sampling knobs AND
    per-request draft windows are ARRAYS, inactive slots are len-0 rows
    the kernel skips — so after the first step the program never retraces
    (``decode_retraces_after_warmup`` asserts it). With
    ``serving_spec_k=K > 0`` the decode step widens from ``[batch]`` to a
    ``[batch, K+1]`` VERIFY frame through the same paged kernel: the host
    n-gram proposer (`drafts.NGramProposer`, no second model) drafts K
    tokens per request, the frame scores every draft position in ONE
    dispatch (per-query causal limits inside the kernel), and the program
    returns the sampled token chain + the accepted-prefix length. Exact
    semantics: position i's token is sampled (or argmax'd) from the same
    logits/PRNG chain plain decode would produce, a draft is accepted iff
    it EQUALS that token, and commits stop at the first mismatch — so the
    committed stream is bit-equal to non-speculative decode, speculation
    only changes how many tokens ONE dispatch commits (1..K+1). Rejected
    drafts' K/V are provisional garbage past the committed length and are
    rewritten before they ever become readable (the PR-9 last-token
    rewrite, widened to the frame head).
  * A small prefill bucket set, with BATCHED PACKED prefill. Admissions
    arriving together are packed into ONE ``[1, frame]`` flash-attention
    frame using PR-5 segment ids (first-fit over 32-aligned rows, one
    page chain per segment), so one program dispatch prefills N short
    prompts instead of N dispatches — pages and streams stay bit-equal
    to sequential prefill. Prompts longer than the frame, adopted-prefix
    tails, and solo arrivals run the chunked path: one request at a time
    in chunks of ``serving_prefill_chunk`` tokens through the same flash
    kernel. Chunk/frame lengths and padded context round up to
    power-of-two buckets, bounding compiles to |chunk buckets| x
    |context buckets| + |frame buckets|. With ``serving_prefix_sharing``
    on, admission adopts the longest indexed committed-prefix pages
    (refcounted, copy-on-write — kv_cache.py) and prefill runs ONLY the
    unmatched tail: a fleet of requests sharing one system prompt
    prefills it once.
  * Disaggregated roles (``serving_role``). A ``decode``-role engine
    with a `disagg.HandoffChannel` attached POSTS fresh full-prompt
    admissions to prefill workers and activates them only on the typed
    KV-page handoff (single-host pools alias, so the handoff is a page
    table splice; copy mode splices extracted pages through the
    compiled restore program). A dead worker or a dropped/overdue
    handoff is RECLAIMED: the decode side re-prefills locally — page
    writes are idempotent byte-identical, so recovery is exactly-once.

Sampling runs inside the decode program (greedy + temperature/top-k/top-p,
per-request RNG keys), so a step's host work is queue bookkeeping plus
O(K) dictionary lookups in the draft proposer.

Chaos: ``serving.spec.verify_mismatch`` (PR-10 registry) zeroes every
row's draft window for the step — a forced full rejection; the engine must
degrade to plain one-token decode, never wedge.

KV memory hierarchy (``serving_kv_cache_dtype`` / ``serving_host_cache_mb``):
the page pools can store int8/fp8 CODES with float32 per-slot-per-head
absmax scales in side pools — writes quantize through the training
observer math, reads dequantize inside the paged kernel, and
``pages_for_budget`` admits ~2x/~4x the sequences at the same HBM budget.
Below HBM sits an optional pinned-host cold tier: committed pages whose
refcount drops to zero DEMOTE (one compiled D2H gather) instead of dying,
and a later radix hit PROMOTES them back (one compiled H2D scatter) —
both standalone programs, so the decode signature never retraces across a
tier transition. ``serving.kv.promote_fail`` chaos degrades a failed
restore to re-prefilling the unmatched tail.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.compile_cache import compile_totals
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.lora.store import AdapterLoadError  # registers swap_fail chaos
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing as obs_tracing
from paddle_tpu.serving.drafts import NGramProposer
from paddle_tpu.serving.kv_cache import (PageAllocator, kv_page_bytes,
                                         pages_for_budget)
from paddle_tpu.serving.sampling import request_key, sample_tokens
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          QueueFull, Request, RequestState)

__all__ = ["ServingConfig", "ServingEngine"]

faults.register(
    "serving.spec.verify_mismatch",
    "forces a speculative verify step to reject every draft (every row's "
    "window zeroed): the engine must degrade to plain one-token decode "
    "for the step — same stream, lower throughput — never wedge")


@dataclass
class ServingConfig:
    page_size: int = 0              # 0 -> FLAGS_serving_page_size
    num_pages: int = 0              # 0 -> FLAGS_serving_num_pages, then
                                    #      derive from hbm_budget_mb
    hbm_budget_mb: int = 0          # 0 -> FLAGS_serving_hbm_budget_mb
    decode_batch: int = 0           # 0 -> FLAGS_serving_decode_batch
    prefill_chunk: int = 0          # 0 -> FLAGS_serving_prefill_chunk
    max_seq_len: int = 0            # 0 -> FLAGS_serving_max_seq_len or model
    kv_dtype: object = None         # None -> model param dtype
    kv_cache_dtype: str = ""        # "" -> FLAGS_serving_kv_cache_dtype
                                    #   ("model" | "int8" | "fp8")
    host_cache_mb: int = -1         # <0 -> FLAGS_serving_host_cache_mb
    sample_seed: int = 0
    max_waiting: int = 0            # 0 -> FLAGS_serving_waiting_queue_limit
    spec_k: int | None = None       # None -> FLAGS_serving_spec_k
    prefix_sharing: bool | None = None  # None -> FLAGS_serving_prefix_sharing
    role: str = ""                  # "" -> FLAGS_serving_role
    prefill_pack: bool | None = None    # None -> FLAGS_serving_prefill_pack
    pack_frame: int = 0             # 0 -> FLAGS_serving_pack_frame,
                                    #      then prefill_chunk

    def resolved(self, model_max_pos: int):
        from paddle_tpu.core.flags import flag

        ps = self.page_size or flag("serving_page_size")
        batch = self.decode_batch or flag("serving_decode_batch")
        chunk = self.prefill_chunk or flag("serving_prefill_chunk")
        smax = (self.max_seq_len or flag("serving_max_seq_len")
                or model_max_pos)
        budget = self.hbm_budget_mb or flag("serving_hbm_budget_mb")
        pages = self.num_pages or flag("serving_num_pages")
        waiting = self.max_waiting or flag("serving_waiting_queue_limit")
        spec_k = (flag("serving_spec_k") if self.spec_k is None
                  else self.spec_k)
        sharing = (flag("serving_prefix_sharing")
                   if self.prefix_sharing is None else self.prefix_sharing)
        kv_mode = (self.kv_cache_dtype
                   or flag("serving_kv_cache_dtype")).lower()
        host_mb = (self.host_cache_mb if self.host_cache_mb >= 0
                   else flag("serving_host_cache_mb"))
        role = (self.role or str(flag("serving_role"))).lower()
        pack = (flag("serving_prefill_pack") if self.prefill_pack is None
                else self.prefill_pack)
        frame = self.pack_frame or flag("serving_pack_frame")
        return (int(ps), int(batch), int(chunk), int(smax), int(budget),
                int(pages), int(waiting), int(spec_k), bool(sharing),
                str(kv_mode), int(host_mb), str(role), bool(pack),
                int(frame))


import itertools as _itertools

_engine_seq = _itertools.count()

# engine stats() fields exposed as gauges (label: engine=<seq>) — the
# /metrics view of the SAME numbers /stats serves (byte-compatible /stats
# stays the probe surface; Prometheus scrapes these)
_ENGINE_GAUGES = (
    "queue_depth", "oldest_wait_age_s", "in_flight", "slot_fill",
    "decode_retraces_after_warmup", "free_pages", "spec_k",
    "accepted_tokens_per_step", "prefix_hit_rate", "cow_copies",
    "prefill_batch_fill", "handoff_ms", "pending_handoffs",
)
_ENGINE_COUNTERS = {
    # monotonic engine totals mirrored at scrape time
    "committed_tokens": "_committed_tokens",
    "decode_steps": "_decode_steps",
    "prefix_matched_tokens": "_prefix_matched_tokens",
    "handoff_pages": "_handoff_pages",
}


def _register_engine_metrics(engine: "ServingEngine"):
    import weakref

    ref = weakref.ref(engine)

    def collect(reg):
        eng = ref()
        if eng is None:
            return
        st = eng.stats()
        for k in _ENGINE_GAUGES:
            reg.gauge(f"serving_engine_{k}",
                      f"ServingEngine.stats()['{k}']",
                      labels=("engine",)).labels(
                engine=eng._metrics_id).set(float(st.get(k, 0) or 0))
        for name, attr in _ENGINE_COUNTERS.items():
            reg.counter(f"serving_engine_{name}_total",
                        f"monotonic engine total: {name}",
                        labels=("engine",)).labels(
                engine=eng._metrics_id)._set_total(
                float(getattr(eng, attr)))
        # PR-16 memory-hierarchy plane: tier occupancy, transition totals
        # and the storage mode as a labeled one-hot
        alloc = eng.allocator
        tiers = reg.gauge("kv_tier_pages",
                          "KV pages resident per tier (hbm counts held + "
                          "cold committed pages; host counts demoted "
                          "pages in the pinned-host pool)",
                          labels=("engine", "tier"))
        tiers.labels(engine=eng._metrics_id, tier="hbm").set(
            float(eng.num_pages - 1 - alloc.free_pages))
        tiers.labels(engine=eng._metrics_id, tier="host").set(
            float(alloc.host_used))
        reg.counter("kv_demotions_total",
                    "KV pages demoted HBM -> host (tier evictions)",
                    labels=("engine",)).labels(
            engine=eng._metrics_id)._set_total(float(alloc.demotions))
        reg.counter("kv_promotions_total",
                    "KV pages promoted host -> HBM (radix-hit restores)",
                    labels=("engine",)).labels(
            engine=eng._metrics_id)._set_total(float(alloc.promotions))
        reg.gauge("kv_cache_dtype",
                  "KV page-pool storage mode (one-hot by dtype label)",
                  labels=("engine", "dtype")).labels(
            engine=eng._metrics_id,
            dtype=st.get("kv_cache_dtype", "unknown")).set(1.0)
        # PR-19 disaggregation: the engine's serving role as a labeled
        # one-hot (prefill/decode/mixed — what router placement filters)
        reg.gauge("serving_engine_role",
                  "engine serving role (one-hot by role label)",
                  labels=("engine", "role")).labels(
            engine=eng._metrics_id,
            role=st.get("role", "mixed")).set(1.0)
        # multi-tenant LoRA billing: committed tokens per tenant (the
        # AdapterStore registers its own residency/swap collectors)
        tok = reg.counter("lora_tokens_total",
                          "committed tokens per tenant (tenant field, "
                          "adapter id fallback)",
                          labels=("engine", "tenant"))
        for tenant, n in st.get("tenant_tokens", {}).items():
            tok.labels(engine=eng._metrics_id,
                       tenant=tenant)._set_total(float(n))

    obs_metrics.registry().add_collector(collect, owner=engine)


def _named(fn, name: str):
    """`fn` under a name of its own: JAX names a program after its function
    (`jit(engine_decode)`), which is how the compile log and a device
    trace's `XLA Modules` tell the engine's programs apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _buckets(lo: int, hi: int) -> list[int]:
    """Power-of-two sizes in [lo, hi] plus hi itself (the compile set)."""
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


def _bucket(n: int, buckets: list[int]) -> int:
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


class ServingEngine:
    """Continuous-batching generation over a decode-capable model (the
    `decode_forward` protocol LlamaForCausalLM implements)."""

    def __init__(self, model, config: ServingConfig | None = None,
                 adapter_store=None):
        self.model = model
        self.config = config or ServingConfig()
        # multi-tenant LoRA: per-row adapter slot ids + the store's pools
        # ride EVERY decode/verify/prefill signature (None placeholders
        # when storeless — None is a static pytree, so both modes share
        # one program shape and neither ever retraces)
        self.adapters = adapter_store
        if adapter_store is not None:
            adapter_store.validate_model(model)
        mcfg = model.config
        self.num_layers = int(mcfg.num_hidden_layers)
        self.num_kv_heads = int(mcfg.num_key_value_heads)
        self.head_dim = int(mcfg.hidden_size) // int(mcfg.num_attention_heads)
        (self.page_size, self.decode_batch, self.prefill_chunk,
         self.max_seq_len, budget_mb, cfg_pages, self.max_waiting,
         self.spec_k, self.prefix_sharing, kv_mode,
         host_mb, role, pack, pack_frame) = self.config.resolved(
            int(mcfg.max_position_embeddings))
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"serving_role must be one of "
                             f"mixed/prefill/decode, got {role!r}")
        self.role = role
        if self.spec_k < 0:
            raise ValueError(f"serving_spec_k must be >= 0, "
                             f"got {self.spec_k}")
        rope_limit = int(getattr(mcfg, "rope_max_position", 0)
                         or mcfg.max_position_embeddings)
        if self.max_seq_len > rope_limit:
            raise ValueError(
                f"serving_max_seq_len={self.max_seq_len} exceeds the hoisted "
                f"RoPE table (rope_max_position={rope_limit}); raise "
                f"LlamaConfig.rope_max_position to serve longer contexts")
        if self.config.page_size == 0:
            # page size IS the paged kernel's K-block granularity, so it
            # resolves through the same shared helper as every other
            # Pallas block knob: explicit FLAGS_serving_page_size >
            # tuned entry > the flag's default (16)
            from paddle_tpu.tuning.blocks import resolve_blocks

            heur = self.page_size
            res = resolve_blocks(
                "paged_attention",
                {"num_kv_heads": self.num_kv_heads,
                 "head_dim": self.head_dim,
                 "max_seq_len": self.max_seq_len},
                default=lambda g: (heur,))
            self.page_size = int(res.values["page_size"])
        self.pages_per_seq = -(-self.max_seq_len // self.page_size)

        params = [p._value for p in model.parameters()]
        for p in params:
            # a CompiledTrainStep DONATES the model's original arrays into
            # its compiled program and keeps the live weights device-side;
            # serving a just-trained model without syncing back would die
            # deep in jit arg-sharding with an opaque "Array has been
            # deleted" — fail at construction with the fix instead
            if getattr(p, "is_deleted", lambda: False)():
                raise ValueError(
                    "model parameters are donated/deleted device arrays — "
                    "call CompiledTrainStep.sync_params_to_model() (or "
                    "reload a checkpoint) before constructing ServingEngine")
        # KV storage mode: "model" stores pages in the weight/kv_dtype
        # (PR-9/12 behavior); "int8"/"fp8" store quantized CODES with
        # per-slot-per-head float32 absmax scales in side pools and the
        # paged kernel dequantizes in VMEM — page_bytes shrinks to 1
        # byte/value, so pages_for_budget admits ~itemsize x the pages
        if kv_mode not in ("model", "int8", "fp8"):
            raise ValueError(f"serving_kv_cache_dtype must be one of "
                             f"model/int8/fp8, got {kv_mode!r}")
        if kv_mode == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
            kv_mode = "int8"   # platform without float8: same contract
        self.kv_mode = kv_mode
        self.kv_quantized = kv_mode != "model"
        if kv_mode == "int8":
            self.kv_dtype = jnp.dtype(jnp.int8)
        elif kv_mode == "fp8":
            self.kv_dtype = jnp.dtype(jnp.float8_e4m3fn)
        else:
            self.kv_dtype = jnp.dtype(self.config.kv_dtype
                                      or params[0].dtype)
        page_bytes = kv_page_bytes(self.num_layers, self.num_kv_heads,
                                   self.page_size, self.head_dim,
                                   self.kv_dtype.itemsize)
        num_pages = cfg_pages or pages_for_budget(budget_mb << 20,
                                                  page_bytes)
        if num_pages - 1 < self.pages_per_seq:
            raise ValueError(
                f"KV pool of {num_pages} pages cannot hold ONE max-length "
                f"request ({self.pages_per_seq} pages); raise "
                f"serving_num_pages/serving_hbm_budget_mb or lower "
                f"serving_max_seq_len")
        self.num_pages = int(num_pages)
        self.kv_cache_bytes = page_bytes * self.num_pages
        # f32 scale side pools (k + v), reported separately from the page
        # budget: 4 bytes per slot per head ~= pool_bytes * 4 / head_dim
        scale_page_bytes = (2 * self.num_layers * self.num_kv_heads
                            * self.page_size * 4) if self.kv_quantized else 0
        self.kv_scale_bytes = scale_page_bytes * self.num_pages

        # host-RAM cold tier: committed-but-idle pages demote here instead
        # of dying; sized by serving_host_cache_mb over FULL page bytes
        # (codes + scales) so the knob is honest about host footprint
        host_page_bytes = page_bytes + scale_page_bytes
        self.host_pages = ((int(host_mb) << 20) // host_page_bytes
                           if host_mb > 0 else 0)

        self.allocator = PageAllocator(self.num_pages, self.page_size,
                                       host_pages=self.host_pages)
        self.scheduler = ContinuousBatchingScheduler(
            self.allocator, self.decode_batch, self.max_seq_len,
            max_waiting=self.max_waiting,
            prefix_sharing=self.prefix_sharing, spec_k=self.spec_k)
        self._proposer = NGramProposer()
        self._params = params
        shape = (self.num_layers, self.num_kv_heads, self.num_pages,
                 self.page_size, self.head_dim)
        # ONE cache pytree (donated through every compiled step as a
        # single argument): k/v page pools, plus the scale side pools
        # when quantized — the model's decode path keys its
        # quantize-on-write behavior off the presence of "k_scale"
        self._cache = {"k": jnp.zeros(shape, self.kv_dtype),
                       "v": jnp.zeros(shape, self.kv_dtype)}
        if self.kv_quantized:
            self._cache["k_scale"] = jnp.zeros(shape[:4], jnp.float32)
            self._cache["v_scale"] = jnp.zeros(shape[:4], jnp.float32)
        # pinned-host backing store for demoted pages, one slot per host
        # page ([slot, L, H, PS, D] so a page is one contiguous row)
        self._host_store = {
            name: np.zeros((self.host_pages, self.num_layers,
                            self.num_kv_heads, self.page_size)
                           + ((self.head_dim,)
                              if name in ("k", "v") else ()),
                           self._cache[name].dtype)
            for name in self._cache
        } if self.host_pages else {}

        self._chunk_buckets = _buckets(min(8, self.prefill_chunk),
                                       self.prefill_chunk)
        self._ctx_buckets = _buckets(min(32, self._ctx_cap()),
                                     self._ctx_cap())
        self._keys: dict[int, np.ndarray] = {}
        self._submit_seq = 0           # per-engine sample-stream identity
        self._decode_traces = 0
        self._prefill_traces = 0
        self._decode_traces_at_warmup: int | None = None
        self._donate = (jax.devices()[0].platform == "tpu")
        self._decode_fn = None
        self._verify_fns: dict[int, object] = {}    # draft window K -> fn
        self._copy_fn = None
        self._extract_fn = None      # D2H demote: gather one page
        self._restore_fn = None      # H2D promote: scatter one page
        self._prefill_fns: dict[tuple[int, int], object] = {}
        # batched packed prefill (PR-19 tentpole): same-arrival short
        # prompts share ONE [1, frame] segment-id flash frame. Segment
        # starts stay 32-row aligned so the packed kernel sees the exact
        # block decomposition sequential prefill would — that alignment
        # is what makes packed page bytes BIT-EQUAL to one-at-a-time.
        self.prefill_pack = bool(pack)
        self.pack_align = 32
        frame = min(int(pack_frame or self.prefill_chunk), self._ctx_cap())
        self.pack_frame = max(self.pack_align,
                              (frame // self.pack_align) * self.pack_align)
        self._pack_buckets = _buckets(min(64, self.pack_frame),
                                      self.pack_frame)
        self._prefill_packed_fns: dict[int, object] = {}
        self._pack_frames = 0
        self._pack_reqs = 0
        self._pack_fill_tokens = 0
        self._pack_frame_tokens = 0
        # KV-page handoff (decode role): admissions parked on the prefill
        # workers until their page chains land (or the reclaim fallback
        # re-prefills locally)
        self._handoff_channel = None
        self._handoff_timeout_s = 5.0
        self._pending_handoff: dict[int, object] = {}
        self._cancelled_pending: set[int] = set()
        self._handoffs = 0
        self._handoff_reclaims = 0
        self._handoff_pages = 0
        self._handoff_ms_total = 0.0
        self._handoff_ms_last = 0.0
        # speculation / prefix-sharing accounting (stats() surfaces these;
        # accepted-tokens/step and prefix-hit-rate read
        # them): committed counts REAL tokens delivered to requests, steps
        # counts decode/verify dispatches, draft_ms the host proposer time
        self._committed_tokens = 0
        self._decode_steps = 0
        self._slot_steps = 0        # sum over steps of active slots
        # per-tenant committed-token billing (tenant field, adapter id
        # fallback) — the lora_tokens_total{tenant=} counter source
        self._tenant_tokens: dict[str, int] = {}
        self._draft_ms = 0.0
        self._prefix_admit_tokens = 0
        self._prefix_matched_tokens = 0
        import threading
        self._http_lock = threading.Lock()
        # serializes device work between this engine's driver and any
        # ALIAS-mode prefill worker writing into the shared pools: every
        # compiled step REASSIGNS (and on TPU donates) the functional
        # cache handle, so concurrent dispatch would fork or kill it
        self._step_lock = threading.RLock()
        # seconds submit() waited for the step lock (stats())
        self._submit_wait_s = 0.0
        self._submit_wait_max_s = 0.0
        self._http_stop = False
        self._http_error: str | None = None
        # observability: register a SCRAPE-TIME collector mapping stats()
        # into the process registry — the decode hot path pays nothing,
        # and the weakref owner unhooks a collected engine automatically
        self._metrics_id = str(next(_engine_seq))
        _register_engine_metrics(self)

    def _ctx_cap(self) -> int:
        return self.pages_per_seq * self.page_size

    # read-only views of the page pools (tests peek at page bytes;
    # the MUTABLE handle is the single donated `_cache` pytree)
    @property
    def _ck(self):
        return self._cache["k"]

    @property
    def _cv(self):
        return self._cache["v"]

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _adapter_bind(self, aslots, apools, bpools):
        """The in-program LoRA binding: inside a traced step, expose the
        traced pool/slot arguments to F.linear via the seam. Storeless
        engines (aslots is None — a STATIC empty pytree) get a no-op, so
        one program body serves both modes without retracing."""
        if self.adapters is not None and aslots is not None:
            return self.adapters.bind(apools, bpools, aslots)
        import contextlib

        return contextlib.nullcontext()

    def _adapter_args(self, aslots):
        """Host-side halves of the adapter signature: the packed per-row
        slot array + the store's current pools (None placeholders when
        storeless, so call sites stay uniform)."""
        if self.adapters is None:
            return None, None, None
        apools, bpools = self.adapters.pools()
        return jnp.asarray(aslots), apools, bpools

    def _bill_tenant(self, req):
        key = req.tenant or req.adapter
        if key:
            self._tenant_tokens[key] = self._tenant_tokens.get(key, 0) + 1

    def _pack_adapter_rows(self, active, b):
        """Per-row adapter slot ids for one packed dispatch — adapter ids
        ride the signature like sampling knobs. Rows without an adapter
        (and empty slots) carry the store's trash id: the grouped matmul
        contributes an exact zero delta for them."""
        if self.adapters is None:
            return None
        rows = np.full(b, self.adapters.num_slots, np.int32)
        for i, req in enumerate(active):
            if req.adapter:
                rows[i] = self.adapters.slot_of(req.adapter)
        return rows

    def _decode(self):
        if self._decode_fn is None:
            from paddle_tpu.parallel.train_step import functional_call

            def fn(params, cache, ids, lens, page_table, keys, temp,
                   top_k, top_p, aslots, apools, bpools):
                self._decode_traces += 1
                positions = jnp.maximum(lens - 1, 0).astype(jnp.int32)
                with self._adapter_bind(aslots, apools, bpools):
                    logits3, cache = functional_call(
                        self.model, params, (ids[:, None],),
                        dict(cache=cache, page_table=page_table,
                             context_lens=lens,
                             position_ids=positions[:, None]),
                        training=False, method="decode_forward")
                logits = logits3._value[:, 0]
                tokens, new_keys = sample_tokens(logits, keys, temp,
                                                 top_k, top_p)
                # logits are consumed by sampling IN-program and not
                # returned: a [batch, vocab] fp32 output would otherwise
                # stay live between steps for nothing
                return tokens, new_keys, cache

            self._decode_fn = jax.jit(
                _named(fn, "engine_decode"),
                donate_argnums=(1,) if self._donate else ())
        return self._decode_fn

    def _prefill(self, chunk_pad: int, ctx_pad: int):
        key = (chunk_pad, ctx_pad)
        if key not in self._prefill_fns:
            from paddle_tpu.parallel.train_step import functional_call

            cap = self._ctx_cap()

            def fn(params, cache, ids, start, total, page_row, aslots,
                   apools, bpools):
                self._prefill_traces += 1
                # pad tokens of the final chunk clamp to the last valid
                # position: they write the one not-yet-valid slot cap-1
                # (rewritten by decode before it's ever readable) instead
                # of wrapping into live slots
                positions = jnp.minimum(
                    start + jnp.arange(chunk_pad, dtype=jnp.int32), cap - 1)
                with self._adapter_bind(aslots, apools, bpools):
                    _, cache = functional_call(
                        self.model, params, (ids[None],),
                        dict(cache=cache,
                             page_table=page_row[None],
                             context_lens=total.reshape(1),
                             position_ids=positions[None], ctx_pad=ctx_pad),
                        training=False, method="decode_forward")
                return cache

            self._prefill_fns[key] = jax.jit(
                _named(fn, f"engine_prefill_{chunk_pad}x{ctx_pad}"),
                donate_argnums=(1,) if self._donate else ())
        return self._prefill_fns[key]

    def _prefill_packed(self, frame: int):
        """The packed MULTI-PROMPT prefill program for one frame bucket:
        token ids, segment ids and segment-local positions ride as
        [frame] arrays, the per-segment page chains as one
        [frame/32 + 1, pages] table (the extra all-null row backs pad and
        gap rows), so ONE compile per bucket serves every packing mix.
        Logits are never sampled — the first decode step's last-token
        rewrite mints each request's first token — so the lm_head matmul
        is dead code XLA eliminates."""
        if frame not in self._prefill_packed_fns:
            from paddle_tpu.parallel.train_step import functional_call

            def fn(params, cache, ids, seg, pos, tables, aslots, apools,
                   bpools):
                self._prefill_traces += 1
                with self._adapter_bind(aslots, apools, bpools):
                    _, cache = functional_call(
                        self.model, params, (ids[None],),
                        dict(cache=cache, page_table=tables,
                             context_lens=jnp.ones(1, jnp.int32),
                             position_ids=pos[None],
                             segment_ids=seg[None]),
                        training=False, method="decode_forward")
                return cache

            self._prefill_packed_fns[frame] = jax.jit(
                _named(fn, f"engine_prefill_packed_{frame}"),
                donate_argnums=(1,) if self._donate else ())
        return self._prefill_packed_fns[frame]

    def _plan_frames(self, seq, length_of):
        """First-fit split into pack frames: each segment consumes
        ceil(len/32)*32 aligned rows, and a segment that would overflow
        the frame starts the next one. Items longer than the frame never
        get here (callers route them to the chunked path)."""
        frames, cur, used = [], [], 0
        for x in seq:
            rows = -(-int(length_of(x)) // self.pack_align) * self.pack_align
            if cur and used + rows > self.pack_frame:
                frames.append(cur)
                cur, used = [], 0
            cur.append(x)
            used += rows
        if cur:
            frames.append(cur)
        return frames

    def packed_prefill_cache(self, cache, items, adapter=None):
        """Device work of ONE packed multi-prompt prefill frame over
        `cache`: `items` is a list of (tokens int32 [L], page_row int32)
        pairs whose page chains live in whichever pool `cache` belongs to
        — this engine's own, or a copy-mode prefill worker's side pool.
        Callers pre-split items with `_plan_frames`. Returns the updated
        cache handle. Pads and inter-segment gap rows carry the null
        segment id (the all-null table row), so their K/V writes land in
        the reserved trash page and their attention contribution is
        masked out by the segment-id kernel."""
        align, ps = self.pack_align, self.page_size
        used = sum(-(-int(t.size) // align) * align for t, _ in items)
        fpad = _bucket(used, self._pack_buckets)
        n_seg = fpad // align       # frame capacity in 32-row segments
        n_pages = -(-fpad // ps)
        ids = np.zeros(fpad, np.int32)
        seg = np.full(fpad, n_seg, np.int32)
        pos = np.zeros(fpad, np.int32)
        tables = np.zeros((n_seg + 1, n_pages), np.int32)
        off = filled = 0
        for j, (toks, row) in enumerate(items):
            t = int(toks.size)
            ids[off:off + t] = toks
            seg[off:off + t] = j
            pos[off:off + t] = np.arange(t, dtype=np.int32)
            n = min(n_pages, int(np.asarray(row).size))
            tables[j, :n] = np.asarray(row)[:n]
            off += -(-t // align) * align
            filled += t
        aslots, apools, bpools = (None, None, None)
        if self.adapters is not None:
            slot = (self.adapters.slot_of(adapter)
                    if adapter else self.adapters.num_slots)
            aslots, apools, bpools = self._adapter_args(
                np.full(1, slot, np.int32))
        cache = self._prefill_packed(fpad)(
            self._params, cache, jnp.asarray(ids), jnp.asarray(seg),
            jnp.asarray(pos), jnp.asarray(tables), aslots, apools, bpools)
        self._pack_frames += 1
        self._pack_reqs += len(items)
        self._pack_fill_tokens += filled
        self._pack_frame_tokens += fpad
        return cache

    def prefill_jobs(self, jobs) -> float:
        """ALIAS-mode prefill-worker entry: run the jobs' packed frames
        straight into this engine's shared pools under the step lock.
        The chains were allocated by the decode side at admission, so
        writes land in pages the target requests already own — and a
        later decode-side re-prefill of the same job is an idempotent
        byte-overwrite, which is what makes reclaim exactly-once.
        Returns device milliseconds spent."""
        items = [(j.tokens, j.page_row) for j in jobs if not j.cancelled]
        t0 = time.perf_counter()
        if items:
            with self._step_lock:
                for frame in self._plan_frames(items,
                                               lambda it: it[0].size):
                    self._cache = self.packed_prefill_cache(self._cache,
                                                            frame)
        return (time.perf_counter() - t0) * 1e3

    def _verify(self, k: int):
        """The [batch, K+1] speculative verify program for draft window
        `k` — compiled once per K (programs are cached, so toggling K at
        runtime never retraces a warmed window)."""
        if k not in self._verify_fns:
            from paddle_tpu.parallel.train_step import functional_call

            t_frame = k + 1
            cap = self._ctx_cap()

            def fn(params, cache, ids, lens, page_table, keys, temp,
                   top_k, top_p, drafts, n_spec, aslots, apools, bpools):
                self._decode_traces += 1
                base = jnp.maximum(lens - 1, 0).astype(jnp.int32)   # [B]
                offs = jnp.arange(t_frame, dtype=jnp.int32)[None]   # [1,T]
                positions = base[:, None] + offs                    # [B,T]
                # frame slot i writes K/V only inside the row's window
                # (i <= n_spec), inside the context cap, and only for
                # active rows; everything else spills to the null page
                write_mask = ((offs <= n_spec[:, None])
                              & (positions < cap)
                              & (lens > 0)[:, None])
                positions = jnp.minimum(positions, cap - 1)
                with self._adapter_bind(aslots, apools, bpools):
                    logits3, cache = functional_call(
                        self.model, params, (ids,),
                        dict(cache=cache, page_table=page_table,
                             context_lens=lens, position_ids=positions,
                             write_mask=write_mask, verify=True),
                        training=False, method="decode_forward")
                logits = logits3._value                           # [B,T,V]
                # the EXACT plain-decode sampling chain, unrolled over the
                # frame: position i draws with the key plain decode would
                # hold after i commits, so the committed stream is
                # bit-equal to non-speculative decode by construction
                toks, carries = [], []
                kc = keys
                for i in range(t_frame):
                    t_i, kc = sample_tokens(logits[:, i], kc, temp,
                                            top_k, top_p)
                    toks.append(t_i)
                    carries.append(kc)
                tokens = jnp.stack(toks, axis=1)                  # [B, T]
                keyc = jnp.stack(carries, axis=1)                 # [B,T,2]
                # a draft is ACCEPTED iff it equals the token the target
                # chain sampled at its position (acceptance probability ==
                # p(draft), the point-mass rejection-sampling rate);
                # commits = accepted prefix + the first divergent sample,
                # which is itself drawn from the exact conditional
                match = ((tokens[:, :k] == drafts)
                         & (jnp.arange(k, dtype=jnp.int32)[None]
                            < n_spec[:, None]))
                accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                               axis=1), axis=1)    # [B]
                new_keys = jnp.take_along_axis(
                    keyc, accepted[:, None, None], axis=1)[:, 0]
                return tokens, accepted, new_keys, cache

            self._verify_fns[k] = jax.jit(
                _named(fn, f"engine_verify_{k}"),
                donate_argnums=(1,) if self._donate else ())
        return self._verify_fns[k]

    def _copy_page(self):
        """One-page copy-on-write program (src/dst ride as arrays — ONE
        compile serves every copy). Copies EVERY pool in the cache pytree,
        so quantized codes and their scales split together."""
        if self._copy_fn is None:
            def fn(cache, src, dst):
                return {name: a.at[:, :, dst].set(a[:, :, src])
                        for name, a in cache.items()}

            self._copy_fn = jax.jit(
                _named(fn, "engine_copy_page"),
                donate_argnums=(0,) if self._donate else ())
        return self._copy_fn

    def _extract_page(self):
        """One-page D2H gather (the demote half of the host tier): returns
        the page's slice of every pool; the caller device_gets it into the
        pinned-host store. Page index rides as an array — ONE compile."""
        if self._extract_fn is None:
            def fn(cache, src):
                return {name: a[:, :, src] for name, a in cache.items()}

            self._extract_fn = jax.jit(_named(fn, "engine_extract_page"))
        return self._extract_fn

    def _restore_page(self):
        """One-page H2D scatter (the promote half): writes a host-stored
        page back into a fresh pool page — the PR-12 copy-program shape
        with the source riding as a transferred array."""
        if self._restore_fn is None:
            def fn(cache, data, dst):
                return {name: a.at[:, :, dst].set(data[name])
                        for name, a in cache.items()}

            self._restore_fn = jax.jit(
                _named(fn, "engine_restore_page"),
                donate_argnums=(0,) if self._donate else ())
        return self._restore_fn

    def configure_speculation(self, spec_k: int | None = None,
                              prefix_sharing: bool | None = None):
        """Runtime toggle for A/B runs on ONE engine (the bench's
        baseline-vs-speculative arms share every compiled program): verify
        programs are cached per K, so switching back to a warmed window
        costs nothing."""
        if spec_k is not None:
            if spec_k < 0:
                raise ValueError(f"spec_k must be >= 0, got {spec_k}")
            turning_on = spec_k > 0 and self.spec_k == 0
            self.spec_k = int(spec_k)
            self.scheduler.spec_k = int(spec_k)
            if turning_on:
                # plain decode neither seeds nor feeds the proposer, so
                # live requests would draft from missing/stale tables
                # (every verify frame fully rejected — (K+1)x compute per
                # committed token). Reseed from each committed stream:
                # tables are a pure function of it, so this is exact.
                for rid, req in self.scheduler._by_rid.items():
                    self._proposer.add_request(rid, req.context)
        if prefix_sharing is not None:
            self.prefix_sharing = bool(prefix_sharing)
            self.scheduler.prefix_sharing = bool(prefix_sharing)

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, eos_id: int | None = None,
               stream_cb=None, adapter: str | None = None,
               tenant: str = "") -> int:
        if adapter and self.adapters is None:
            raise AdapterLoadError(
                adapter, "engine was constructed without an AdapterStore")
        if adapter:
            # pin BEFORE the scheduler sees the request: the slot must be
            # resident for every dispatch this request rides, and a failed
            # load must cost one typed error, never a queued-then-wedged
            # request (unpinned on the QueueFull race below and in
            # release())
            self.adapters.acquire(adapter)
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      eos_id=eos_id, stream_cb=stream_cb,
                      adapter=adapter, tenant=tenant)
        # pool sufficiency is a CONSTRUCTOR invariant (>= pages_per_seq
        # usable pages), so any request within serving_max_seq_len fits
        # alone; the scheduler enforces the length limit
        # under _step_lock: a concurrent step() must never see the request
        # as admittable before its RNG key (and draft table) exist — the
        # submit-vs-step gap was a real KeyError under bursty feeders
        t0 = time.perf_counter()
        with obs_tracing.span("engine.submit_wait", component="engine"):
            # a driver holds the lock for the whole of step() and takes it
            # again at once: what a submitter waits here is time to first
            # token that no scheduler counter sees
            self._step_lock.acquire()
        try:
            waited = time.perf_counter() - t0
            self._submit_wait_s += waited
            self._submit_wait_max_s = max(self._submit_wait_max_s, waited)
            try:
                rid = self.scheduler.submit(req)
            except Exception:
                if adapter:
                    self.adapters.release(adapter)
                raise
            self._keys[rid] = self._new_key()
            if self.spec_k > 0:
                self._proposer.add_request(rid, req.prompt)
        finally:
            self._step_lock.release()
        return rid

    def _new_key(self) -> np.ndarray:
        # keyed by per-engine submission ORDER (not the process-global rid):
        # re-running the same request sequence with the same seed reproduces
        # the same sampled streams in any process
        key = request_key(self.config.sample_seed, self._submit_seq)
        self._submit_seq += 1
        return np.asarray(key, np.uint32)

    def cancel(self, rid: int) -> bool:
        job = self._pending_handoff.get(rid)
        if job is not None:
            # the rid's pages are an in-flight prefill-worker target:
            # freeing them now could reallocate them under a write. Mark
            # and defer — handoff resolution finishes the cancel on the
            # decode thread once the writes are settled.
            job.cancelled = True
            self._cancelled_pending.add(rid)
            return True
        return self.scheduler.cancel(rid)

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------
    def _run_prefill(self, req: Request):
        with obs_tracing.span("engine.prefill", component="engine",
                              trace_id=(req.trace_id or None), rid=req.rid,
                              tokens=int(req.context.size),
                              matched=int(req.matched_tokens)):
            self._run_prefill_inner(req)

    def _run_prefill_inner(self, req: Request):
        ctx = req.context
        total = int(ctx.size)
        row = jnp.asarray(self.allocator.page_table_row(
            req.rid, self.pages_per_seq))
        # prefix sharing: the adopted pages already hold the matched
        # prefix's committed K/V — prefill runs ONLY the unmatched tail
        # (chunk attention still gathers the WHOLE context back from the
        # pages, shared ones included, so the tail attends to the shared
        # prefix exactly as if it had been prefilled here). A full match
        # skips prefill entirely; the first decode step's last-token
        # rewrite (CoW'd if the page is shared) keeps the stream exact.
        off = int(req.matched_tokens)
        self._prefix_admit_tokens += total
        self._prefix_matched_tokens += off
        aslots, apools, bpools = (None, None, None)
        if self.adapters is not None:
            slot = (self.adapters.slot_of(req.adapter)
                    if req.adapter else self.adapters.num_slots)
            aslots, apools, bpools = self._adapter_args(
                np.full(1, slot, np.int32))
        while off < total:
            t = min(self.prefill_chunk, total - off)
            cpad = _bucket(t, self._chunk_buckets)
            ctx_pad = _bucket(min(off + cpad, self._ctx_cap()),
                              self._ctx_buckets)
            ids = np.zeros(cpad, np.int32)
            ids[:t] = ctx[off:off + t]
            fn = self._prefill(cpad, ctx_pad)
            self._cache = fn(
                self._params, self._cache, jnp.asarray(ids),
                jnp.asarray(off, jnp.int32),
                jnp.asarray(off + t, jnp.int32), row,
                aslots, apools, bpools)
            off += t

    def _run_prefill_packed(self, reqs):
        """One packed frame prefilling `reqs` together — bit-equal to
        running `_run_prefill` per request (same kernel, same 32-row
        block decomposition), amortizing one program dispatch over N."""
        items = []
        for r in reqs:
            self._prefix_admit_tokens += int(r.context.size)
            items.append((np.asarray(r.context, np.int32),
                          self.allocator.page_table_row(
                              r.rid, self.pages_per_seq)))
        with obs_tracing.span(
                "engine.prefill_packed", component="engine",
                reqs=len(reqs), tokens=sum(int(t.size) for t, _ in items),
                trace_ids=[r.trace_id for r in reqs if r.trace_id]):
            self._cache = self.packed_prefill_cache(
                self._cache, items, adapter=reqs[0].adapter)

    def _decode_once(self, active, finisher):
        """Pack `active` requests into the fixed decode-batch signature,
        run ONE compiled decode step, and apply the sampled tokens —
        shared verbatim by the continuous scheduler and the static-batch
        baseline so both provably run the same program. `finisher(req)`
        releases a request that just hit its stop condition."""
        # the host's four parts of a decode step, each a span of its own
        # (docs/observability.md): only `readback` waits for the device
        span = obs_tracing.span
        b, pmax = self.decode_batch, self.pages_per_seq
        with span("engine.decode.pack", component="engine"):
            ids = np.zeros(b, np.int32)
            lens = np.zeros(b, np.int32)
            pt = np.zeros((b, pmax), np.int32)
            keys = np.zeros((b, 2), np.uint32)
            temp = np.zeros(b, np.float32)
            top_k = np.zeros(b, np.int32)
            top_p = np.ones(b, np.float32)
            arows = self._pack_adapter_rows(active, b)
            for i, req in enumerate(active):
                # NOT req.context[-1]: that concatenates prompt+generated
                # every step (O(len) per token -> O(len^2) per stream)
                ids[i] = (req.generated[-1] if req.generated
                          else int(req.prompt[-1]))
                lens[i] = req.total_len
                pt[i] = self.allocator.page_table_row(req.rid, pmax)
                keys[i] = self._keys[req.rid]
                temp[i] = req.temperature
                top_k[i] = req.top_k
                top_p[i] = req.top_p
        with span("engine.decode.dispatch", component="engine"):
            aslots, apools, bpools = self._adapter_args(arows) \
                if arows is not None else (None, None, None)
            tokens, new_keys, self._cache = self._decode()(
                self._params, self._cache, jnp.asarray(ids),
                jnp.asarray(lens), jnp.asarray(pt), jnp.asarray(keys),
                jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
                aslots, apools, bpools)
        with span("engine.decode.readback", component="engine"):
            toks = np.asarray(tokens)
            nkeys = np.asarray(new_keys)
        with span("engine.decode.apply", component="engine"):
            for i, req in enumerate(active):
                tok = int(toks[i])
                req.generated.append(tok)
                self._keys[req.rid] = nkeys[i]
                self._bill_tenant(req)
                if req.stream_cb is not None:
                    req.stream_cb(req, tok)
                if ((req.eos_id is not None and tok == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens):
                    finisher(req)
            self._committed_tokens += len(active)
            self._slot_steps += len(active)
            self._decode_steps += 1

    def _verify_once(self, active, finisher):
        """Pack `active` requests into the fixed [batch, K+1] verify
        signature, run ONE compiled verify step, and commit the accepted
        token runs — the speculative sibling of `_decode_once` (same
        program role, 1..K+1 committed tokens per request per dispatch)."""
        b, pmax, k = self.decode_batch, self.pages_per_seq, self.spec_k
        t_frame = k + 1
        cap = self._ctx_cap()
        ids = np.zeros((b, t_frame), np.int32)
        drafts = np.zeros((b, k), np.int32)
        n_spec = np.zeros(b, np.int32)
        lens = np.zeros(b, np.int32)
        pt = np.zeros((b, pmax), np.int32)
        keys = np.zeros((b, 2), np.uint32)
        temp = np.zeros(b, np.float32)
        top_k = np.zeros(b, np.int32)
        top_p = np.ones(b, np.float32)
        # chaos: a forced FULL rejection — every window zeroed, the frame
        # degrades to plain one-token decode for this step
        chaos_reject = faults.fire_check("serving.spec.verify_mismatch")
        t_draft = time.perf_counter()
        for i, req in enumerate(active):
            ids[i, 0] = (req.generated[-1] if req.generated
                         else int(req.prompt[-1]))
            lens[i] = req.total_len
            pt[i] = self.allocator.page_table_row(req.rid, pmax)
            keys[i] = self._keys[req.rid]
            temp[i] = req.temperature
            top_k[i] = req.top_k
            top_p[i] = req.top_p
            # the row's draft window: never past the request's remaining
            # budget (commits = window+1 at most) nor the context cap
            # (frame writes reach position total_len-1+window)
            n = min(k, req.max_new_tokens - len(req.generated) - 1,
                    cap - req.total_len)
            if chaos_reject or n <= 0:
                continue
            prop = self._proposer.propose(req.rid, n)
            drafts[i, :n] = prop
            ids[i, 1:1 + n] = prop
            n_spec[i] = n
        self._draft_ms += (time.perf_counter() - t_draft) * 1e3
        arows = self._pack_adapter_rows(active, b)
        aslots, apools, bpools = self._adapter_args(arows) \
            if arows is not None else (None, None, None)
        tokens, accepted, new_keys, self._cache = self._verify(k)(
            self._params, self._cache, jnp.asarray(ids),
            jnp.asarray(lens), jnp.asarray(pt), jnp.asarray(keys),
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(drafts), jnp.asarray(n_spec),
            aslots, apools, bpools)
        toks = np.asarray(tokens)
        acc = np.asarray(accepted)
        nkeys = np.asarray(new_keys)
        for i, req in enumerate(active):
            # the verified chain: accepted drafts + the first divergent
            # (or bonus) sample — each token is exactly what plain decode
            # would have produced, so streaming/eos/budget handling is
            # token-by-token identical
            self._keys[req.rid] = nkeys[i]
            for tok in toks[i, :int(acc[i]) + 1]:
                tok = int(tok)
                req.generated.append(tok)
                self._committed_tokens += 1
                self._bill_tenant(req)
                if self.spec_k > 0:
                    self._proposer.observe(req.rid, tok)
                if req.stream_cb is not None:
                    req.stream_cb(req, tok)
                if ((req.eos_id is not None and tok == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens):
                    finisher(req)
                    break
        self._slot_steps += len(active)
        self._decode_steps += 1

    def _apply_cow(self):
        """Apply the scheduler's pending copy-on-write page copies
        device-side (src keeps the sharers; dst is the writer's private
        copy — byte-identical at the moment of the split)."""
        copies = self.scheduler.pending_cow
        if not copies:
            return
        self.scheduler.pending_cow = []
        fn = self._copy_page()
        for src, dst in copies:
            self._cache = fn(self._cache,
                             jnp.asarray(src, jnp.int32),
                             jnp.asarray(dst, jnp.int32))

    def _apply_tier_ops(self):
        """Drain the allocator's queued tier transitions: demotes (D2H —
        a reclaimed cold page's bytes move to the pinned-host store BEFORE
        anything overwrites the device page) then promotes (H2D — a
        radix-hit host page restores into its fresh pool page). Ordering
        contract with the allocator: this runs after every admission/grow
        and before any prefill/decode/CoW device write, so tier copies are
        standalone compiled programs and the decode step NEVER retraces
        across a transition."""
        if not self.allocator.tier_enabled:
            return
        demotes, promotes = self.allocator.take_tier_ops()
        if not demotes and not promotes:
            return
        extract = self._extract_page()
        restore = self._restore_page()
        for page, slot in demotes:
            data = extract(self._cache, jnp.asarray(page, jnp.int32))
            for name, arr in data.items():
                self._host_store[name][slot] = np.asarray(arr)
        for slot, page in promotes:
            data = {name: store[slot]
                    for name, store in self._host_store.items()}
            self._cache = restore(self._cache, data,
                                  jnp.asarray(page, jnp.int32))
        # journal the batch (storms — many transitions in one drain — at
        # warning severity so dashboards notice thrash, not each page)
        sev = "warn" if len(demotes) + len(promotes) >= 8 else "info"
        if demotes:
            obs_events.emit("serving", "kv_demote", severity=sev,
                            pages=len(demotes),
                            host_used=self.allocator.host_used)
        if promotes:
            obs_events.emit("serving", "kv_promote", severity=sev,
                            pages=len(promotes),
                            host_used=self.allocator.host_used)

    def _packable(self, req: Request) -> bool:
        return (self.prefill_pack
                and req.matched_tokens == 0
                and int(req.context.size) <= self.pack_frame)

    def _postable(self, req: Request) -> bool:
        # adapter'd requests prefill locally (one slot id rides the
        # packed frame; cross-engine slot residency is not a worker
        # contract), as do adopted-prefix tails and over-frame prompts
        return (req.matched_tokens == 0 and not req.adapter
                and int(req.context.size) <= self.pack_frame)

    def _pack_collides(self, head: Request, batch) -> bool:
        """Would the waiting head prefix-match a collected-but-unflushed
        batch member? Packing past that point would lose the adoption
        (pages register only at flush), so the caller flushes first."""
        if not self.prefix_sharing:
            return False
        ps = self.page_size
        ctx = head.context
        if int(ctx.size) < ps:
            return False
        h = np.asarray(ctx[:ps])
        return any(int(r.context.size) >= ps
                   and np.array_equal(np.asarray(r.context[:ps]), h)
                   for r in batch)

    def _admit(self):
        """Admission phase: drain the waiting queue into prefills.

        Packable same-arrival admissions (fresh full prompts that fit
        the pack frame) COLLECT into a batch flushed as packed
        segment-id frames; everything else — adopted-prefix tails,
        prompts longer than the frame, an adapter change mid-batch, a
        waiting head that would prefix-match a collected member —
        flushes first and runs the chunked one-at-a-time path, keeping
        the PR-14 contract that a request's pages are registered before
        the next prefix match runs.

        A decode-role engine with live prefill workers POSTS packable
        admissions instead: the page chain is allocated here, the writes
        happen on the worker, and activation waits for the typed
        KV-page handoff (or the reclaim fallback re-prefills locally)."""
        batch: list[Request] = []

        def flush():
            if not batch:
                return
            self._apply_tier_ops()
            for frame in self._plan_frames(batch,
                                           lambda r: r.context.size):
                if len(frame) == 1:
                    # a frame of one gains nothing over the chunked path
                    # and would cost an extra compile bucket: solo
                    # arrivals keep the exact PR-9 program sequence
                    self._run_prefill(frame[0])
                else:
                    self._run_prefill_packed(frame)
            for r in batch:
                if self.prefix_sharing:
                    self.allocator.register_prefix(r.rid, r.context)
                self.scheduler.activate(r)
            batch.clear()

        post_ok = (self._handoff_channel is not None
                   and self._handoff_channel.workers_alive())
        while True:
            # collected batch members and posted-but-unlanded handoffs
            # hold decode slots the scheduler can't see yet: account for
            # them here or collection would overcommit the batch
            if (len(self.scheduler.running) + len(batch)
                    + len(self._pending_handoff) >= self.decode_batch):
                break
            head = (self.scheduler.waiting[0]
                    if self.scheduler.waiting else None)
            if head is None:
                break
            if batch and self._pack_collides(head, batch):
                flush()
                continue
            admitted = self.scheduler.admissions(limit=1)
            if not admitted:
                break
            req = admitted[0]
            if post_ok and self._postable(req):
                flush()
                self._post_prefill(req)
                continue
            if self._packable(req):
                if batch and ((req.adapter or None)
                              != (batch[0].adapter or None)):
                    flush()
                batch.append(req)
                continue
            flush()
            # tier transitions queued by this admission's match/ensure
            # (promoted radix hits, demoted reclaim victims) must land
            # before the tail prefill touches the device pools
            self._apply_tier_ops()
            self._run_prefill(req)
            if self.prefix_sharing:
                # a request's committed context becomes matchable the
                # moment its pages are written: the next admission
                # sharing the prefix adopts them instead of re-prefilling
                self.allocator.register_prefix(req.rid, req.context)
            self.scheduler.activate(req)
        flush()

    def step(self) -> bool:
        """One scheduler iteration: handoff ingest (decode role),
        admissions (+ their packed/chunked prefills and prefix
        registration), chain growth/eviction + copy-on-write, then ONE
        packed decode step — the [batch] plain-decode program, or the
        [batch, K+1] speculative verify frame when serving_spec_k > 0.
        Returns False when nothing is running (idle or waiting-only)."""
        with self._step_lock:
            return self._step_locked()

    def _step_locked(self) -> bool:
        if self._handoff_channel is not None:
            self._drain_handoffs()
        with obs_tracing.span("engine.admit", component="engine"):
            self._admit()
            self.scheduler.grow()
            self._apply_tier_ops()   # grow()'s reclaims demote before CoW
            self._apply_cow()
        running = list(self.scheduler.running)
        if not running:
            if self._pending_handoff:
                # every admitted request is parked on the prefill
                # workers: wait a beat for a handoff instead of spinning
                self._drain_handoffs(wait_s=0.002)
                return True
            if self.scheduler.waiting:
                blocked = self.scheduler.waiting[0]
                raise RuntimeError(
                    f"serving deadlock: request {blocked.rid} "
                    f"({blocked.total_len + 1} tokens) cannot be admitted "
                    f"with {self.allocator.free_pages} free pages "
                    f"({self.allocator.reclaimable_pages} reclaimable incl. "
                    f"cold) and nothing left to evict")
            return False
        if obs_tracing.tracing_active():
            # one span per packed dispatch, carrying EVERY active request's
            # trace id — the decode-step end of the router->...->decode
            # correlation chain (attr cost only paid while tracing)
            name = ("engine.verify_step" if self.spec_k > 0
                    else "engine.decode_step")
            with obs_tracing.span(
                    name, component="engine", slots=len(running),
                    trace_ids=[r.trace_id for r in running if r.trace_id],
                    rids=[r.rid for r in running]):
                if self.spec_k > 0:
                    self._verify_once(running, self.scheduler.finish)
                else:
                    self._decode_once(running, self.scheduler.finish)
        elif self.spec_k > 0:
            self._verify_once(running, self.scheduler.finish)
        else:
            self._decode_once(running, self.scheduler.finish)
        return True

    # ------------------------------------------------------------------
    # disaggregation: the decode side of the KV-page handoff
    # ------------------------------------------------------------------
    @property
    def busy(self) -> bool:
        """Work pending anywhere: scheduler queues OR admissions parked
        on the prefill workers. Drivers must keep stepping for the
        latter — `scheduler.idle` alone would strand them (a pending
        handoff is neither waiting nor running)."""
        return (not self.scheduler.idle) or bool(self._pending_handoff)

    def attach_prefill(self, channel, timeout_s: float | None = None):
        """Wire a `disagg.HandoffChannel` into this engine (the decode
        role): packable fresh admissions are POSTED as prefill jobs and
        activate only on the typed KV-page handoff. An overdue, dropped
        or worker-death-orphaned job is RECLAIMED by a local re-prefill:
        page writes are idempotent byte-overwrites into pages this
        engine's request already owns, so a worker that died mid-write
        cannot corrupt the stream — recovery is exactly-once."""
        from paddle_tpu.core.flags import flag

        self._handoff_channel = channel
        self._handoff_timeout_s = float(
            flag("serving_handoff_timeout_s") if timeout_s is None
            else timeout_s)

    def _post_prefill(self, req: Request):
        from paddle_tpu.serving.disagg import PrefillJob

        # tier ops queued by this admission must land before a worker
        # writes into the freshly ensured chain
        self._apply_tier_ops()
        job = PrefillJob(
            rid=req.rid,
            tokens=np.asarray(req.context, np.int32),
            page_row=np.asarray(self.allocator.page_table_row(
                req.rid, self.pages_per_seq), np.int32),
            posted_t=time.monotonic(),
            trace_id=req.trace_id or "")
        self._pending_handoff[req.rid] = job
        self._handoff_channel.post(job)

    def _drain_handoffs(self, wait_s: float = 0.0):
        ch = self._handoff_channel
        for h in ch.take_done(wait_s):
            self._ingest_handoff(h)
        if not self._pending_handoff:
            return
        now = time.monotonic()
        alive = ch.workers_alive()
        stale = [job for job in list(self._pending_handoff.values())
                 if job.failed or not alive
                 or now - job.posted_t > self._handoff_timeout_s]
        for job in stale:
            self._reclaim(job)

    def _ingest_handoff(self, h):
        job = self._pending_handoff.pop(h.rid, None)
        if job is None:
            return            # already reclaimed locally: exactly-once
        req = self.scheduler._by_rid.get(h.rid)
        if req is None or h.rid in self._cancelled_pending:
            self._finish_cancelled(h.rid)
            return
        if h.pages is not None:
            # copy mode: splice the worker's extracted pages into this
            # pool's chain through the compiled restore program (the
            # PR-16 promote shape — the "one compiled device-to-device
            # copy program" of the handoff contract)
            restore = self._restore_page()
            chain = self.allocator.chain(h.rid)
            for data, dst in zip(h.pages, chain):
                self._cache = restore(self._cache, data,
                                      jnp.asarray(dst, jnp.int32))
        self._handoffs += 1
        self._handoff_pages += int(h.n_pages)
        self._handoff_ms_total += float(h.ms)
        self._handoff_ms_last = float(h.ms)
        self._prefix_admit_tokens += int(req.context.size)
        obs_events.emit(
            "serving", "handoff", rid=int(h.rid), pages=int(h.n_pages),
            ms=round(float(h.ms), 3), worker=h.worker,
            mode="copy" if h.pages is not None else "alias")
        if self.prefix_sharing:
            self.allocator.register_prefix(req.rid, req.context)
        self.scheduler.activate(req)

    def _reclaim(self, job):
        self._pending_handoff.pop(job.rid, None)
        job.cancelled = True      # a live worker skips it if still queued
        req = self.scheduler._by_rid.get(job.rid)
        if req is None or job.rid in self._cancelled_pending:
            self._finish_cancelled(job.rid)
            return
        self._handoff_reclaims += 1
        obs_events.emit("serving", "handoff_reclaim", severity="warn",
                        rid=int(job.rid),
                        cause="worker_failed" if job.failed else "timeout")
        self._apply_tier_ops()
        self._run_prefill(req)
        if self.prefix_sharing:
            self.allocator.register_prefix(req.rid, req.context)
        self.scheduler.activate(req)

    def _finish_cancelled(self, rid: int):
        """The deferred cancel+release for a request whose pages were an
        in-flight prefill-worker target when its client went away:
        resolution runs on the decode thread with the writes settled, so
        the pages are finally safe to free."""
        self._cancelled_pending.discard(rid)
        if self.scheduler._by_rid.get(rid) is None:
            return
        self.scheduler.cancel(rid)
        self.scheduler.release(rid)
        self._keys.pop(rid, None)
        self._proposer.drop(rid)

    def run_until_idle(self, max_steps: int = 1_000_000):
        steps = 0
        while self.busy:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"serving loop exceeded {max_steps} steps")
        return steps

    def release(self, rid: int):
        """Drop a finished request's bookkeeping (scheduler entry, RNG
        key, draft tables, adapter slot pin) — the per-request memory a
        long-lived server must not retain."""
        if rid in self._pending_handoff or rid in self._cancelled_pending:
            # deferred alongside cancel(): handoff resolution runs the
            # real cleanup once the worker's writes are settled
            return
        req = self.scheduler._by_rid.get(rid)
        if (req is not None and req.finished and req.adapter
                and self.adapters is not None):
            # unpin exactly once: scheduler.release drops the _by_rid
            # entry for finished requests, so a second release is a no-op
            self.adapters.release(req.adapter)
        self.scheduler.release(rid)
        self._keys.pop(rid, None)
        self._proposer.drop(rid)

    def generate(self, prompts, max_new_tokens: int = 16, **kw):
        """Synchronous convenience: submit all, run to completion, return
        the generated token lists in submission order."""
        rids = [self.submit(p, max_new_tokens=max_new_tokens, **kw)
                for p in prompts]
        self.run_until_idle()
        outs = [list(self.scheduler.get(r).generated) for r in rids]
        for r in rids:
            self.release(r)
        return outs

    # ------------------------------------------------------------------
    # static-batch baseline (the strawman the tests compare with)
    # ------------------------------------------------------------------
    def static_batch_generate(self, prompts, max_new_tokens, **kw):
        """Naive static batching: groups of `decode_batch` requests run to
        COLLECTIVE completion before the next group starts — a finished
        request's slot idles until the group's straggler is done. Same
        compiled decode program; only the scheduling differs."""
        new_tokens = (list(max_new_tokens)
                      if isinstance(max_new_tokens, (list, tuple, np.ndarray))
                      else [max_new_tokens] * len(prompts))
        reqs = [Request(prompt=p, max_new_tokens=int(n), **kw)
                for p, n in zip(prompts, new_tokens)]
        for req in reqs:
            self._keys[req.rid] = self._new_key()
        def finish_static(req):
            req.state = RequestState.FINISHED
            self.allocator.free_request(req.rid)

        for g0 in range(0, len(reqs), self.decode_batch):
            group = reqs[g0:g0 + self.decode_batch]
            for req in group:
                if not self.allocator.ensure(
                        req.rid, req.prompt.size + req.max_new_tokens):
                    raise RuntimeError("static baseline: KV pool too small "
                                       "for one full batch")
                req.state = RequestState.RUNNING
                req.admitted_t = time.perf_counter()
                self._apply_tier_ops()
                self._run_prefill(req)
            while any(not r.finished for r in group):
                self._decode_once([r for r in group if not r.finished],
                                  finish_static)
        for req in reqs:      # static requests never enter the scheduler
            self._keys.pop(req.rid, None)
        return reqs

    # ------------------------------------------------------------------
    # HTTP front-end (the /generate endpoint of inference/serve.py)
    # ------------------------------------------------------------------
    def _http_generate(self, payload: dict, deadline: float):
        """Generator of stream events for one /generate request: a driver
        thread turns the scheduler, per-token callbacks land in a queue,
        and this generator drains it until completion / deadline (deadline
        cancels the request so its pages free immediately)."""
        import queue as queue_mod

        q = queue_mod.Queue()
        adapter_err = None
        with self._http_lock:
            try:
                rid = self.submit(
                    np.asarray(payload["prompt_ids"], np.int32),
                    max_new_tokens=int(payload.get("max_new_tokens", 16)),
                    temperature=float(payload.get("temperature", 0.0)),
                    top_k=int(payload.get("top_k", 0)),
                    top_p=float(payload.get("top_p", 1.0)),
                    eos_id=payload.get("eos_id"),
                    stream_cb=lambda req, tok: q.put(tok),
                    adapter=payload.get("adapter"),
                    tenant=str(payload.get("tenant") or ""))
            except QueueFull:
                # admission raced past the pre-headers check: headers are
                # already out, so the refusal becomes the ONE terminal
                # stream event (with the same Retry-After semantics)
                rid = None
            except AdapterLoadError as e:
                # a failed adapter load degrades to ONE typed terminal
                # event for THIS request — the engine, the batch and every
                # other tenant's stream are untouched
                rid = None
                adapter_err = e
            else:
                req = self.scheduler.get(rid)
                # the trace id rides the request object like sampling
                # knobs: spans from prefill down to the decode step carry it
                req.trace_id = str(payload.get("trace") or "")
        if rid is None:
            from paddle_tpu.core.flags import flag

            if adapter_err is not None:
                yield {"error": "adapter_load_failed",
                       "adapter": adapter_err.adapter_id,
                       "message": str(adapter_err)}
            else:
                yield {"error": "queue_full",
                       "retry_after": float(flag("router_retry_after_s"))}
            return
        n = 0
        try:
            while True:
                # the deadline bounds STREAMING requests too, not just
                # stalls — a max_new_tokens large enough to outlive the
                # budget is cut off mid-stream and its pages freed
                if time.monotonic() > deadline:
                    yield {"rid": rid, "error": "timeout", "tokens": n}
                    return
                if self._http_error is not None:
                    # the driver thread died: fail fast instead of letting
                    # every stream idle out to its deadline
                    yield {"rid": rid, "error": self._http_error,
                           "tokens": n}
                    return
                try:
                    tok = q.get(timeout=0.05)
                except queue_mod.Empty:
                    if req.finished and q.empty():
                        break
                    continue
                n += 1
                yield {"rid": rid, "token": int(tok)}
                if req.finished and q.empty():
                    break
            yield {"rid": rid, "done": True, "tokens": n,
                   "state": req.state.value}
        finally:
            # runs on normal completion, timeout, driver error AND
            # generator teardown (client disconnect -> GeneratorExit at a
            # yield): an abandoned request must stop occupying its decode
            # slot and KV pages immediately
            with self._http_lock:
                if not req.finished:
                    self.cancel(rid)
                self.release(rid)

    def _drive_http(self):
        while not self._http_stop:
            try:
                with self._http_lock:
                    busy = self.busy
                    if busy:
                        self.step()
            except Exception as e:  # surface through every open stream
                self._http_error = f"serving driver died: " \
                                   f"{type(e).__name__}: {e}"
                return
            if not busy:
                time.sleep(0.002)

    def _http_admit(self, payload: dict) -> dict | None:
        """serve.py's `admit_fn` contract: refuse BEFORE response headers
        when the waiting queue is at its bound, so the common case of
        sustained overload gets a clean 503 + Retry-After instead of a
        200 whose stream immediately carries a queue_full error event
        (that in-stream path remains only for the submit race)."""
        from paddle_tpu.core.flags import flag

        depth = self.scheduler.queue_depth
        if self.max_waiting and depth >= self.max_waiting:
            return {"status": 503,
                    "retry_after": float(flag("router_retry_after_s")),
                    "message": f"serving waiting queue full ({depth} "
                               f"queued >= {self.max_waiting})"}
        return None

    def _http_health(self) -> dict:
        """/healthz: liveness (driver thread state) + the readiness
        snapshot. ok=False once the driver died — probes see the corpse
        without waiting for a generate call to fail."""
        h = {"ok": self._http_error is None, **self.stats()}
        if self._http_error is not None:
            h["error"] = self._http_error
        return h

    def serve_http(self, port: int, block: bool = True):
        """Serve POST /generate (streaming ndjson token events) through the
        hardened HTTP front-end in paddle_tpu.inference.serve — the
        scheduler runs on a driver thread, handler threads only queue
        requests and drain token streams. GET /healthz and /stats answer
        the same readiness fields the fleet router probes."""
        import threading

        from paddle_tpu.core.flags import flag
        from paddle_tpu.distributed.resilience import faults
        from paddle_tpu.inference.serve import build_http_server

        # standalone serving processes validate FLAGS_fault_injection at
        # startup too (the supervisor/fit contract): a typo'd chaos spec
        # fails HERE, not at whichever injection site fires first
        faults.check_flag_spec()

        srv = build_http_server(
            port, generate_fn=self._http_generate,
            queue_limit=int(flag("serving_queue_limit")),
            timeout_s=float(flag("serving_request_timeout_s")),
            max_body_bytes=int(flag("serving_max_body_mb")) << 20,
            admit_fn=self._http_admit, health_fn=self._http_health,
            stats_fn=self.stats,
            metrics_fn=lambda: obs_metrics.registry().prometheus_text())
        self._http_stop = False
        driver = threading.Thread(target=self._drive_http,
                                  name="paddle_tpu.serving.driver",
                                  daemon=True)
        driver.start()
        self._http_driver = driver
        self._http_server = srv
        if block:  # pragma: no cover - CLI path
            try:
                srv.serve_forever()
            finally:
                self.shutdown_http()
        return srv

    def shutdown_http(self):
        self._http_stop = True
        driver = getattr(self, "_http_driver", None)
        if driver is not None:
            driver.join(timeout=5.0)
            self._http_driver = None
        srv = getattr(self, "_http_server", None)
        if srv is not None:
            srv.shutdown()
            srv.server_close()
            self._http_server = None

    # ------------------------------------------------------------------
    # instrumentation
    # ------------------------------------------------------------------
    def mark_warmup(self):
        """Call after the first real decode step: any trace past this point
        is a retrace bug (`decode_retraces_after_warmup`)."""
        self._decode_traces_at_warmup = self._decode_traces

    @property
    def decode_retraces_after_warmup(self) -> int:
        if self._decode_traces_at_warmup is None:
            return 0
        return self._decode_traces - self._decode_traces_at_warmup

    @property
    def decode_traces(self) -> int:
        return self._decode_traces

    @property
    def prefill_traces(self) -> int:
        return self._prefill_traces

    def stats(self) -> dict:
        """Readiness snapshot — the fields /stats serves and the fleet
        router's probes consume (queue depth, oldest wait age, slot fill,
        retraces-after-warmup), so liveness/readiness never needs a
        generate call. Lock-free BY DESIGN: every read is a GIL-atomic
        int or a list snapshot, so a probe answers even while the driver
        thread holds the step lock mid-decode."""
        running = len(self.scheduler.running)
        return {
            "queue_depth": self.scheduler.queue_depth,
            "oldest_wait_age_s": round(self.scheduler.oldest_wait_age(), 4),
            "in_flight": running + self.scheduler.queue_depth,
            "slot_fill": round(running / max(self.decode_batch, 1), 4),
            "decode_retraces_after_warmup": self.decode_retraces_after_warmup,
            "free_pages": self.allocator.free_pages,
            "waiting_limit": self.max_waiting,
            # PR-12: REAL-token accounting — with speculation one dispatch
            # commits 1..K+1 tokens per slot, so slot_fill alone
            # understates delivered throughput; routers/dashboards should
            # watermark on accepted tokens, not steps
            "spec_k": self.spec_k,
            "accepted_tokens_per_step": self.accepted_tokens_per_step,
            "prefix_hit_rate": self.prefix_hit_rate,
            "cow_copies": self.allocator.cow_copies,
            "draft_ms_total": round(self._draft_ms, 3),
            # PR-16 memory hierarchy: storage mode + tier occupancy and
            # transition totals (the /stats view of the tier gauges)
            "kv_cache_dtype": (self.kv_mode if self.kv_quantized
                               else self.kv_dtype.name),
            "kv_scale_bytes": self.kv_scale_bytes,
            "kv_cold_pages": self.allocator.cold_pages,
            "kv_host_pages": self.host_pages,
            "kv_host_used": self.allocator.host_used,
            "kv_demotions": self.allocator.demotions,
            "kv_promotions": self.allocator.promotions,
            "kv_cold_hits": self.allocator.cold_hits,
            "kv_promote_failures": self.allocator.promote_failures,
            # multi-tenant LoRA: adapter residency + per-tenant billing
            # (empty placeholders storeless, so /stats keys are stable)
            "lora": (self.adapters.residency()
                     if self.adapters is not None else {}),
            "tenant_tokens": dict(self._tenant_tokens),
            # PR-19 disaggregation: serving role, packed-frame fill, and
            # the KV-page handoff counters (the /stats view of the
            # handoff gauges; routers filter placement on "role")
            "role": self.role,
            "prefill_batch_fill": self.prefill_batch_fill,
            "prefill_packed_frames": self._pack_frames,
            "prefill_packed_requests": self._pack_reqs,
            "pending_handoffs": len(self._pending_handoff),
            "handoffs": self._handoffs,
            "handoff_reclaims": self._handoff_reclaims,
            "handoff_pages": self._handoff_pages,
            "handoff_ms": round(self._handoff_ms_last, 3),
            "handoff_ms_total": round(self._handoff_ms_total, 3),
            # what submit() waited for the step lock: time to first token
            # spent before the scheduler has the request
            "submit_lock_wait_ms_total": round(self._submit_wait_s * 1e3, 3),
            "submit_lock_wait_ms_max": round(
                self._submit_wait_max_s * 1e3, 3),
            # JAX's compile log for the engine's programs (process-wide:
            # every engine's programs are named jit(engine_...)); all zero
            # until core.compile_cache.start_compile_log()
            "compile": compile_totals("jit(engine_"),
        }

    @property
    def prefill_batch_fill(self) -> float:
        """Mean packed-frame fill: real prompt tokens over padded frame
        rows across packed prefill dispatches (1.0 = no padding waste;
        0.0 until the first packed frame)."""
        return round(self._pack_fill_tokens / self._pack_frame_tokens, 4) \
            if self._pack_frame_tokens else 0.0

    @property
    def accepted_tokens_per_step(self) -> float:
        """Committed (real) tokens per OCCUPIED SLOT per dispatch — 1.0
        for plain decode, up to K+1 with perfect draft acceptance
        (normalized by slot-steps, so batching can't inflate it)."""
        return round(self._committed_tokens / self._slot_steps, 4) \
            if self._slot_steps else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admission context tokens covered by adopted shared
        prefix pages (prefill skipped for exactly these tokens)."""
        return round(self._prefix_matched_tokens
                     / self._prefix_admit_tokens, 4) \
            if self._prefix_admit_tokens else 0.0

    @property
    def draft_ms_total(self) -> float:
        return self._draft_ms

    def reset_stats(self):
        self._committed_tokens = 0
        self._decode_steps = 0
        self._slot_steps = 0
        self._draft_ms = 0.0
        self._prefix_admit_tokens = 0
        self._prefix_matched_tokens = 0
        self._pack_frames = 0
        self._pack_reqs = 0
        self._pack_fill_tokens = 0
        self._pack_frame_tokens = 0
        self._handoffs = 0
        self._handoff_reclaims = 0
        self._handoff_pages = 0
        self._handoff_ms_total = 0.0
        self._handoff_ms_last = 0.0
        self.allocator.cow_copies = 0
        self.allocator.prefix_matches = 0
        self.allocator.prefix_tokens_matched = 0
        self.allocator.demotions = 0
        self.allocator.promotions = 0
        self.allocator.cold_hits = 0
        self.allocator.dropped_cold = 0
        self.allocator.promote_failures = 0
