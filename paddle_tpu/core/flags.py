"""Global flag registry.

Reference parity: the gflags-compatible registry in paddle/common/flags.{h,cc}
(registration macro flags.h:343) + `paddle.set_flags`/`get_flags`
(python/paddle/base/framework.py:109). Flags are registered with a type, default
and help string; values can be overridden from the environment via ``FLAGS_<name>``.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable

__all__ = ["define_flag", "set_flags", "get_flags", "flag", "flags_snapshot",
           "flag_explicit"]

_lock = threading.Lock()


@dataclass
class _Flag:
    name: str
    type: type
    default: Any
    help: str
    value: Any
    explicit: bool = False


_REGISTRY: dict[str, _Flag] = {}


def _coerce(typ: type, raw: Any) -> Any:
    if typ is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return typ(raw)


def define_flag(name: str, default: Any, help: str = "", type: type | None = None):
    """Register a flag. Environment variable FLAGS_<name> overrides the default."""
    typ = type or (bool if isinstance(default, bool) else default.__class__)
    with _lock:
        if name in _REGISTRY:
            return _REGISTRY[name]
        value = default
        explicit = False
        env = os.environ.get(f"FLAGS_{name}")
        if env is not None:
            value = _coerce(typ, env)
            explicit = True
        f = _Flag(name, typ, default, help, value, explicit)
        _REGISTRY[name] = f
        return f


def set_flags(flags: dict):
    """paddle.set_flags analog: update registered flags by name (with or without FLAGS_ prefix)."""
    for key, val in flags.items():
        name = key[6:] if key.startswith("FLAGS_") else key
        with _lock:
            if name not in _REGISTRY:
                raise KeyError(f"unknown flag: {key}")
            f = _REGISTRY[name]
            f.value = _coerce(f.type, val)
            f.explicit = True


def get_flags(keys) -> dict:
    if isinstance(keys, str):
        keys = [keys]
    out = {}
    for key in keys:
        name = key[6:] if key.startswith("FLAGS_") else key
        if name not in _REGISTRY:
            raise KeyError(f"unknown flag: {key}")
        out[key] = _REGISTRY[name].value
    return out


def flag(name: str):
    """Fast read of a flag's current value."""
    return _REGISTRY[name].value


def flag_explicit(name: str) -> bool:
    """True when the flag was set by the user (env FLAGS_<name> at import or
    a set_flags call) rather than sitting at its registered default. The
    tuning resolver uses this to rank 'explicit FLAGS override' above a
    tuning-cache hit for flags whose default is a real value (not a 0/auto
    sentinel), e.g. serving_page_size."""
    return _REGISTRY[name].explicit


def flags_snapshot() -> dict:
    with _lock:
        return {k: f.value for k, f in _REGISTRY.items()}


# --- core flags (analogs of the most-used FLAGS_* in the reference) ---
define_flag("check_nan_inf", False, "check outputs for nan/inf after each op (eager)")
define_flag("eager_op_jit", True, "jit-cache single-op executables in eager dispatch")
define_flag("default_device", "", "override default device, e.g. 'tpu' or 'cpu'")
define_flag("allocator_strategy", "auto_growth", "allocator strategy label (XLA manages HBM)")
define_flag("tpu_matmul_precision", "default", "jax matmul precision: default|high|highest")
define_flag("use_pallas_attention", True, "use the Pallas flash-attention kernel when available")
define_flag("flash_block_q", 0, "flash-attention Q tile override (0 = auto-tuned default)", type=int)
define_flag("flash_block_k", 0, "flash-attention K tile override (0 = auto-tuned default)", type=int)
define_flag("flash_bwd_block_q", 0, "flash-attention BACKWARD Q tile override (0 = same as forward)", type=int)
define_flag("flash_bwd_block_k", 0, "flash-attention BACKWARD K tile override (0 = same as forward)", type=int)
define_flag("flash_segment_block_skip", True,
            "segment-aware flash attention: skip whole K blocks whose "
            "segment-id range cannot intersect the Q block's (packed "
            "sequences; escape hatch: set False to mask in-block only)")
define_flag("use_fused_cross_entropy", True,
            "chunked fused softmax-CE fast path in F.cross_entropy (escape hatch: set False)")
define_flag("use_fused_head_loss", True,
            "fuse LM-head projection + CE in models/pipeline head stages (escape hatch: set False)")
define_flag("fused_ce_chunk_tokens", 0, "fused-CE token chunk override, forward and backward (0 = auto: ~4M-element forward tiles, the backward's own depth)", type=int)
define_flag("fused_ce_chunk_vocab", 0, "fused-CE vocab chunk override (0 = auto)", type=int)
define_flag("fused_ce_variant", "auto", "fused-CE strategy: auto|tokens|vocab|pallas")
define_flag("moe_dispatch", "capacity",
            "default MoELayer dispatch mode, consulted when the layer is "
            "constructed with dispatch=None: 'capacity' (fixed [E, C, d] "
            "buckets, overflow tokens dropped and counted) or 'dropless' "
            "(sort-based ragged dispatch through the Pallas grouped "
            "matmul — no capacity, no drops; docs/moe.md)")
define_flag("moe_block_rows", 0,
            "grouped-matmul row-block size of the dropless MoE dispatch "
            "(0 = auto: 128 stepping down for tiny problems); expert "
            "bucket starts are aligned to this, so it is also the "
            "per-expert padding granularity", type=int)
define_flag("moe_gmm_backend", "auto",
            "grouped-matmul backend: auto|pallas|xla — auto runs the "
            "Pallas kernel on TPU (or under force_interpret()) and the "
            "block-gather XLA fallback elsewhere")
define_flag("scan_layers", False,
            "run homogeneous decoder stacks as ONE lax.scan over layer-stacked "
            "params (O(1)-in-depth HLO size and compile time)")
define_flag("prefetch_to_device_depth", 2,
            "double-buffered device prefetch depth for DeviceFeeder/"
            "Model.fit: batches collated + sharded-device_put on a "
            "background thread, this many in flight (0 disables the feeder; "
            "each unit costs one batch of HBM)", type=int)
define_flag("async_dispatch_window", 2,
            "max un-fetched compiled steps in flight before the dispatcher "
            "blocks on the oldest loss (bounds run-ahead HBM)", type=int)
define_flag("metrics_sync_every", 1,
            "read the loss to host every k steps (1 = every step, the "
            "synchronous default; larger k keeps JAX async dispatch "
            "unbroken between reads)", type=int)
define_flag("step_telemetry", False,
            "honest per-step training telemetry: the compiled step returns "
            "a small metrics side-pytree (fp32 loss, global grad-norm, "
            "found_inf/skip flag, fp8 amax watermark) settled lazily on "
            "the host — docs/observability.md; consulted when "
            "CompiledTrainStep(collect_metrics=None)")
define_flag("zero3_gather", "ahead",
            "ZeRO-3 sharded-weights gather schedule in the scan layer loop: "
            "'ahead' = double-buffered gather of layer k+1 while layer k "
            "computes (comm/compute overlap, <=2 layers of full weights "
            "live); 'start' = all-gather the whole stack up front (the "
            "overlap-free baseline)")
define_flag("remat_policy", "none",
            "default selective-rematerialization policy, consulted when a "
            "step is constructed with remat=None (the CompiledTrainStep "
            "default): none|full|save_dots|save_nothing|offload_residuals")
define_flag("fp8_policy", "none",
            "low-precision matmul policy for the step runtimes, consulted "
            "when a step is constructed with fp8_policy=None: none|matmuls|"
            "matmuls+head. 'matmuls' runs F.linear projections (QKV/O/MLP) "
            "through float8_e4m3 (grads float8_e5m2); '+head' also "
            "quantizes the fused-CE head projection (softmax stats stay "
            "fp32)")
define_flag("fp8_amax_history_len", 16,
            "delayed-scaling amax history length per fp8 matmul callsite "
            "(the scale maps max(history) to the fp8 dtype max)", type=int)
define_flag("ckpt_fault_injection", "",
            "LEGACY alias for the unified fault registry "
            "(distributed.resilience.faults): arms 'ckpt.<value>' in "
            "always-fire mode — one of after_snapshot|after_shard_write|"
            "after_metadata|before_rename|before_commit|after_commit; "
            "empty = off. Prefer FLAGS_fault_injection='ckpt.<point>'")
define_flag("fault_injection", "",
            "unified fault-injection spec: ';'-separated armings of "
            "registered points, each 'name[:opts]' with opts nth=K | p=X "
            "| seed=N | mode=once|always (default one-shot), e.g. "
            "'feeder.collate' or 'ckpt.before_rename:nth=8;"
            "step.grads:p=0.05,seed=7'. Catalog: resilience.faults"
            ".describe() / docs/resilience.md")
define_flag("anomaly_detection", False,
            "compiled-step anomaly detection default (consulted when a "
            "step is constructed with anomaly_detector=None): compute the "
            "in-program health scalar (NaN/inf loss or grads; unhealthy "
            "steps skip the optimizer update) and feed the host-side "
            "loss-spike detector")
define_flag("anomaly_policy", "rollback",
            "default escalation policy of a flag-constructed "
            "AnomalyDetector: warn|skip_batch|rollback|halt "
            "(docs/resilience.md)")
define_flag("anomaly_window", 32,
            "rolling loss window (finite losses) behind the median+MAD "
            "spike detector", type=int)
define_flag("anomaly_mad_k", 12.0,
            "loss-spike threshold: flag losses above "
            "median + k * 1.4826 * MAD of the rolling window", type=float)
define_flag("anomaly_min_history", 8,
            "finite losses required in the window before spike detection "
            "activates (non-finite detection is always on)", type=int)
define_flag("scaler_max_consecutive_skips", 100,
            "GradScaler: halt (FloatingPointError) after this many "
            "CONSECUTIVE inf-skip steps — a permanently-NaN model must "
            "stop, not silently skip forever (a warning fires at half "
            "this count; 0 disables both)", type=int)
define_flag("store_barrier_retries", 2,
            "TCPStore barrier: bounded retry-with-backoff attempts after "
            "a timed-out wait before escalating the TimeoutError to the "
            "caller (the watchdog save-and-exit path)", type=int)
define_flag("store_heartbeat_interval_s", 5.0,
            "RankHeartbeat beat interval: each rank refreshes its "
            "__hb__/<job>/<rank> liveness key this often so dead_peers() "
            "can NAME a dead rank within ~2 intervals", type=float)
define_flag("ckpt_keep_last", 3,
            "committed elastic snapshots retained per checkpoint root "
            "(older ones are GC'd after each commit; 0 keeps all)", type=int)
define_flag("ckpt_every_steps", 0,
            "hapi Model.fit(auto_checkpoint=...) cadence: async-save every "
            "k train batches (0 = epoch ends only)", type=int)
define_flag("serving_page_size", 16,
            "KV-cache page size in tokens (block granularity of the paged "
            "decode-attention kernel and the serving allocator)", type=int)
define_flag("serving_num_pages", 0,
            "total KV-cache pages in the serving pool (page 0 is the "
            "reserved null page); 0 = derive from serving_hbm_budget_mb "
            "and the model geometry", type=int)
define_flag("serving_hbm_budget_mb", 64,
            "HBM budget for the paged KV cache when serving_num_pages=0: "
            "the pool is sized to the largest page count whose K+V bytes "
            "across all layers fit the budget", type=int)
define_flag("serving_decode_batch", 8,
            "fixed decode-batch width of the serving engine: every decode "
            "step runs this many slots (inactive ones masked), so the "
            "compiled step has ONE signature and never retraces", type=int)
define_flag("serving_prefill_chunk", 256,
            "max tokens per prefill chunk; prompts longer than this run "
            "through the flash kernel in several page-writing chunks "
            "(bounds per-admission latency and the compile bucket set)",
            type=int)
define_flag("serving_max_seq_len", 0,
            "max context length (prompt + generated) a served request may "
            "reach; 0 = the model's max_position_embeddings. Sets "
            "pages_per_seq = ceil(max_seq_len / page_size)", type=int)
define_flag("serving_queue_limit", 32,
            "bounded HTTP request queue: connections beyond this many "
            "in-flight handler threads are answered 503 instead of "
            "head-of-line blocking the listener", type=int)
define_flag("serving_request_timeout_s", 60.0,
            "per-request wall-clock budget of the HTTP front-end; a /run "
            "or /generate exceeding it is cut off with 503/timeout event",
            type=float)
define_flag("serving_max_body_mb", 8,
            "Content-Length cap of the HTTP front-end (413 past it; "
            "chunked/unknown-length bodies are rejected with 411)",
            type=int)
define_flag("serving_spec_k", 0,
            "speculative decoding draft window: the n-gram self-draft "
            "proposer proposes this many tokens per request per step and "
            "ONE [batch, K+1] verify pass through the paged kernel accepts "
            "the longest agreeing prefix (exact greedy/temperature "
            "semantics — streams are bit-equal to plain decode); 0 = off "
            "(the PR-9 one-token decode step)", type=int)
define_flag("serving_prefix_sharing", 1,
            "copy-on-write shared-prefix KV page reuse: admission matches "
            "the longest committed-full-page prefix of the new context in "
            "the allocator's radix index and links those pages (refcounted)"
            " into the new chain, so prefill runs only the unmatched tail "
            "and one physical page backs every sharer of a common system "
            "prompt; writes into shared pages copy-on-write. 0 = off",
            type=int)
define_flag("serving_kv_cache_dtype", "model",
            "KV page-pool storage dtype: 'model' stores pages in the "
            "weight dtype (PR-9/12 behavior), 'int8'/'fp8' store quantized "
            "codes with per-slot-per-head absmax scales in a float32 side "
            "pool and dequantize INSIDE the paged kernel — int8 halves/"
            "quarters page bytes so pages_for_budget admits ~2x/~4x the "
            "sequences at the same HBM budget ('fp8' falls back to int8 "
            "when the platform lacks float8)")
define_flag("serving_host_cache_mb", 0,
            "host-RAM cold tier for committed KV pages: when > 0, pages "
            "whose refcount drops to zero but remain in the prefix index "
            "are DEMOTED to a pinned-host pool of this many MB instead of "
            "freed, and a later radix hit restores them via one compiled "
            "H2D copy; 0 = off (cold pages stay in HBM until reclaimed)",
            type=int)
define_flag("serving_waiting_queue_limit", 128,
            "bound on the scheduler's WAITING queue (distinct from the "
            "HTTP handler queue): submissions past this many queued "
            "requests raise the typed QueueFull, which the front-end/"
            "router maps to 503 + Retry-After instead of growing the "
            "queue without limit; 0 = unbounded (legacy)", type=int)
define_flag("serving_role", "mixed",
            "serving engine role in a disaggregated fleet: 'mixed' (one "
            "engine prefills AND decodes — the single-host default), "
            "'prefill' (a packed-prefill worker replica the router never "
            "routes /generate traffic to), or 'decode' (a decode worker "
            "that, when a handoff channel is attached, delegates fresh "
            "prompt prefills to prefill workers and ingests their KV-page "
            "handoffs)")
define_flag("serving_prefill_pack", 1,
            "batched packed prefill: admissions arriving together are "
            "packed into ONE [1, frame] flash-attention frame with PR-5 "
            "segment ids (first-fit over 32-aligned rows) instead of "
            "prefilling one request at a time — pages and streams stay "
            "bit-equal to sequential prefill; prompts longer than the "
            "frame (or with an adopted prefix) still run the chunked "
            "path; 0 = always chunked (PR-9 behavior)", type=int)
define_flag("serving_pack_frame", 0,
            "packed-prefill frame length in tokens (rounded down to the "
            "32-row pack alignment); 0 = serving_prefill_chunk. Bounds "
            "the packed compile set to the power-of-two buckets <= frame",
            type=int)
define_flag("serving_handoff_timeout_s", 5.0,
            "decode-worker patience for a posted prefill job: past this "
            "(or on prefill-worker death) the decode engine RECLAIMS the "
            "request and re-prefills locally — the exactly-once fallback "
            "that makes a lost handoff cost latency, never a stream",
            type=float)
define_flag("router_probe_interval_s", 0.25,
            "router health-monitor cadence: each replica's health()/"
            "readiness (queue depth, slot fill, retraces) is probed this "
            "often, and heartbeat liveness (dead_peers) is re-read on the "
            "same tick", type=float)
define_flag("router_failure_threshold", 3,
            "consecutive dispatch/probe failures that trip a replica's "
            "circuit breaker OPEN (dispatches stop routing to it)",
            type=int)
define_flag("router_breaker_cooldown_s", 1.0,
            "seconds an OPEN replica circuit waits before HALF-OPEN: one "
            "trial dispatch is let through; success closes the circuit, "
            "failure re-opens it for another cooldown", type=float)
define_flag("router_dispatch_attempts", 3,
            "total dispatch attempts per request (first try + failover "
            "re-dispatches); past this the request returns ONE typed "
            "error event instead of retrying forever", type=int)
define_flag("router_backoff_initial_s", 0.05,
            "first failover re-dispatch backoff; doubles per retry up to "
            "router_backoff_max_s", type=float)
define_flag("router_backoff_max_s", 1.0,
            "failover re-dispatch backoff ceiling", type=float)
define_flag("router_gap_timeout_s", 5.0,
            "max silence between consecutive stream events from a "
            "replica before the router declares it wedged FOR THIS "
            "REQUEST and fails over (also the detection bound for a "
            "dropped dispatch)", type=float)
define_flag("router_max_inflight", 64,
            "router admission cap: requests in flight across all "
            "replicas; past it new requests are refused with 503 + "
            "Retry-After at admission (before any replica dispatch)",
            type=int)
define_flag("router_shed_queue_depth", 32,
            "overload shed watermark: when aggregate depth (router "
            "in-flight + probed replica queue depths) exceeds this, the "
            "shed policy caps max_new_tokens instead of dropping "
            "requests", type=int)
define_flag("router_shed_max_new_tokens", 32,
            "max_new_tokens cap applied by the shed policy under "
            "overload (degrade before drop)", type=int)
define_flag("router_retry_after_s", 1.0,
            "Retry-After seconds advertised on admission-control 503s",
            type=float)
define_flag("router_placement", "session",
            "replica placement key: 'session' rendezvous-hashes the "
            "session id (PR-11 behavior — one user sticks to one replica), "
            "'prefix' rendezvous-hashes a bounded digest of the prompt's "
            "first router_prefix_tokens ids (session id as tiebreak when "
            "no prompt is present), so requests sharing a system prompt "
            "land where its KV pages already live and the per-replica "
            "prefix-hit rate becomes a fleet-wide property; 'adapter' "
            "rendezvous-hashes the request's LoRA adapter id (session "
            "fallback when none), so one tenant's requests land where "
            "their adapter is already resident in the slot pool")
define_flag("router_prefix_tokens", 64,
            "prompt-prefix digest length (tokens) for "
            "router_placement=prefix: long enough to separate distinct "
            "system prompts, short enough that a shared preamble maps all "
            "its requests to one digest", type=int)
define_flag("router_tenant_max_inflight", 0,
            "per-tenant in-flight fairness cap at router admission: one "
            "tenant (request 'tenant' field, adapter id fallback) may hold "
            "at most this many concurrent streams — past it the request is "
            "refused with a typed 'tenant_limit' event + Retry-After, so a "
            "flooding tenant cannot starve the shared engine; 0 = off",
            type=int)
define_flag("serving_adapter_slots", 16,
            "LoRA AdapterStore HBM slot-pool size: how many adapters can "
            "be RESIDENT (servable) at once per engine; registered "
            "adapters beyond this page host<->HBM on demand (LRU over "
            "refcount-0 slots, pinned slots never evicted)", type=int)
define_flag("rmsnorm_block_rows", 0,
            "Pallas fused-RMSNorm row-block override (0 = auto: 256, "
            "clamped to the row count); resolved through the shared "
            "tuning.blocks helper like every kernel block knob", type=int)
define_flag("autotune", "off",
            "block-size tuning mode of the shared kernel resolver "
            "(tuning.blocks.resolve_blocks): 'off' = heuristics/flags "
            "only (the zero-surprise default), 'load' = consult the JSON "
            "tuning cache under FLAGS_tuning_cache_dir and fall back to "
            "the heuristic on miss, 'search' = on miss ALSO time the "
            "legal block lattice now, persist the winner, and use it "
            "(docs/autotuning.md)")
define_flag("tuning_cache_dir", "",
            "directory of the JSON block-shape tuning cache consumed by "
            "FLAGS_autotune=load|search; empty disables the cache tier "
            "of the resolver")
