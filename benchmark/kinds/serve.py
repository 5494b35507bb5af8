"""A serving cell: an open-loop generator thread submits to
`ServingEngine.submit(..., stream_cb=)` while a driver thread runs
`engine.step()` the way the program's own `_drive_http` does."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import check, harness, reference, traffic

DRAIN_S = 60.0


def build(cell: dict, seed: int):
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = harness.seeded_model(cell["model"], seed)
    model.eval()
    engine = ServingEngine(model, ServingConfig(**cell["engine"]))
    return model, engine


def prefill_programs(engine, shortest: int, longest: int) -> dict:
    """{(chunk bucket, context bucket): shortest prompt that reaches it}, for
    every prompt or re-prefilled context of shortest..longest tokens that
    arrives alone: the engine's own chunking (`_run_prefill_inner`) followed
    on paper, with its own bucket lists."""
    from paddle_tpu.serving.engine import _bucket

    keys = {}
    for total in range(shortest, longest + 1):
        off = 0
        while off < total:
            t = min(engine.prefill_chunk, total - off)
            cpad = _bucket(t, engine._chunk_buckets)
            key = (cpad, _bucket(min(off + cpad, engine._ctx_cap()), engine._ctx_buckets))
            keys.setdefault(key, total)
            off += t
    return keys


def warm_up(engine, mix: dict, vocab: int):
    """Every program this cell's traffic can reach, and no other: each
    chunked-prefill program by one prompt alone, each packed frame by two
    prompts together, the decode program by all of them."""
    rng = np.random.default_rng(0)
    shortest, longest = traffic.warmup_lengths(mix)
    longest = max(longest, engine.max_seq_len - 2)   # an evicted request prefills again
    for total in sorted(set(prefill_programs(engine, shortest, longest).values())):
        engine.generate([rng.integers(1, vocab, total).astype(np.int32)], max_new_tokens=2)
    if engine.prefill_pack:
        for frame in engine._pack_buckets:
            if frame // 2 >= shortest or frame == engine._pack_buckets[0]:
                pair = [rng.integers(1, vocab, frame // 2).astype(np.int32) for _ in range(2)]
                engine.generate(pair, max_new_tokens=2)
    engine.mark_warmup()


class Window:
    """One measured window over a warmed engine."""

    def __init__(self, engine, requests: list[dict], seconds: float,
                 trace: bool = False, trace_s: float = 0.0):
        self.engine, self.seconds = engine, seconds
        self.trace, self.trace_s = trace, trace_s
        self.records = [{"due_s": r["due_s"], "prompt": r["prompt"],
                         "max_new_tokens": r["max_new_tokens"], "times": [],
                         "tokens": [], "submit_t": None, "req": None} for r in requests]
        self.by_rid: dict[int, dict] = {}
        self.error = None
        self._stop = False

    def _on_token(self, req, tok):
        rec = self.by_rid[req.rid]
        rec["times"].append(time.perf_counter())
        rec["tokens"].append(int(tok))
        rec["req"] = req

    def _drive(self):
        try:
            while not self._stop:
                if self.engine.busy:
                    with harness.annotate("engine.step"):
                        self.engine.step()
                else:
                    time.sleep(0.0005)
        except BaseException as e:   # handed to the main thread, which raises it
            self.error = e

    def done(self, rec) -> bool:
        return len(rec["tokens"]) >= rec["max_new_tokens"]

    def run(self):
        driver = threading.Thread(target=self._drive, name="bench.driver", daemon=True)
        self.t0 = time.perf_counter()
        self.profile = harness.Profile(self.trace, self.t0, self.seconds, self.trace_s)
        driver.start()
        try:
            for rec in self.records:
                due = self.t0 + rec["due_s"]
                with harness.annotate("loadgen.wait"):
                    while True:
                        now = time.perf_counter()
                        self.profile.poll(now)
                        if now >= due or self.error:
                            break
                        time.sleep(min(due - now, 0.005))
                if self.error:
                    break
                # registered before submit: the driver may deliver at once
                with self.engine._step_lock:
                    rid = self.engine.submit(rec["prompt"], rec["max_new_tokens"],
                                             stream_cb=self._on_token)
                    self.by_rid[rid] = rec
                rec["submit_t"] = time.perf_counter()
            close = self.t0 + self.seconds
            while time.perf_counter() < close and not self.error:
                self.profile.poll(time.perf_counter())
                time.sleep(0.005)
            self.profile.stop()
            # an answer that comes late is late, not wrong: wait for each
            while (time.perf_counter() < close + DRAIN_S and not self.error
                   and not all(self.done(r) for r in self.records if r["submit_t"])):
                time.sleep(0.01)
        finally:
            self.profile.stop()
            self._stop = True
            driver.join(timeout=120)
        self.t_end = time.perf_counter()
        if driver.is_alive():
            raise RuntimeError("the driver thread did not stop")
        if self.error:
            raise self.error

    # ---- what a user of the system sees -----------------------------------
    def end_to_end(self) -> dict:
        close = self.t0 + self.seconds
        ttft, gaps, delivered = [], [], 0
        for rec in self.records:
            due = self.t0 + rec["due_s"]
            # a request that never answered misses: it waited to the end
            ttft.append((rec["times"][0] if rec["times"] else self.t_end) - due)
            gaps += list(np.diff(rec["times"]))
            delivered += sum(t <= close for t in rec["times"])
        return {"serve_out_tokens_per_s": delivered / self.seconds,
                "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else 0.0,
                "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
                "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)) if gaps else 0.0,
                "n_gaps": len(gaps)}


def sample_for_check(window: Window, seed: int, spec: dict):
    """The finished requests whose served tokens are compared: the longest,
    then others drawn from the seed, `requests` in all (drawn again if fewer
    finished)."""
    finished = [r for r in window.records if r["submit_t"] and window.done(r)]
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 3])
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    order = list(rng.permutation(len(rest)))
    picked = [longest] + [rest[i] for i in order[:spec["requests"] - 1]]
    while len(picked) < spec["requests"]:
        picked.append(picked[len(picked) % len(finished)])
    return picked


def run(cell: dict, args, device: dict, meter, t_start: float) -> dict:
    import jax

    from paddle_tpu.observability import tracing as obs_tracing

    model_cfg, mix = cell["model"], cell["mix"]
    model, engine = build(cell, args.seed)
    warm_up(engine, mix, model_cfg["vocab_size"])
    requests = traffic.requests(mix, args.seed, args.seconds, model_cfg["vocab_size"])
    facts = {"kv_pages": engine.num_pages, "kv_cache_bytes": engine.kv_cache_bytes,
             "prefill_programs": engine.prefill_traces}
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.1f}s, compile {meter.secs:.1f}s, cache hits {getattr(meter, 'hits', 0)} "
                f"misses {getattr(meter, 'misses', 0)}, {facts}, {len(requests)} requests")
    traces_before, compile_s = meter.traces, meter.secs

    window = Window(engine, requests, args.seconds, bool(args.trace),
                    cell.get("trace_s", 4.0))
    if args.trace:
        obs_tracing.start_tracing()
    try:
        window.run()
    finally:
        spans = obs_tracing.stop_tracing() if args.trace else []
    in_window = meter.traces - traces_before
    peak = harness.memory_peak(device["used"])
    stats = engine.stats()
    retraces = engine.decode_retraces_after_warmup
    e2e = window.end_to_end()
    never = sum(1 for r in window.records if not window.done(r))
    harness.log(f"window closed: {e2e}, never came {never}, peak {peak / 2**30:.2f} GiB, "
                f"compiles in window {in_window}, decode retraces {retraces}, "
                f"drain {window.t_end - window.t0 - args.seconds:.1f}s")
    waits = [r["req"].admitted_t - r["req"].arrival_t for r in window.records if r["req"]]
    admitted = [(r["req"].admitted_t, len(r["prompt"])) for r in window.records if r["req"]]
    picked = sample_for_check(window, args.seed, cell["check"])

    # ---- free the program, then the reference ------------------------------
    for r in window.records:
        r["req"] = None
    del model, engine, window.engine
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    numbers = {"never_came": never, "compiles_in_window": in_window + retraces}
    seqs = [np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)]) for r in picked]
    if picked:
        gaps = reference.served_logit_gaps(
            model_cfg, args.seed, seqs, [len(r["prompt"]) for r in picked],
            pad_to=cell["engine"]["max_seq_len"], param_dtype=model_cfg["dtype"])
        numbers["logit_gap"] = float(max(g.max() for g in gaps["served"]))
        numbers["checked_tokens"] = int(sum(len(g) for g in gaps["served"]))
    else:
        numbers["logit_gap"] = float("inf")
    checks = check.judge(numbers, cell["limits"])
    harness.log(f"reference {time.perf_counter() - t_ref:.1f}s over {numbers.get('checked_tokens', 0)} tokens")
    return {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(window.records), "failed": never,
        "end_to_end": dict(e2e, setup_s=setup_s),
        "checks": checks, "memory_peak_bytes": peak, "profile": window.profile,
        "run": {"cell": cell, "device": device, "window_s": args.seconds,
                "t0": window.t0, "records": window.records, "spans": spans,
                "queue_waits": waits, "admitted": admitted, "stats": stats,
                "facts": facts, "compile_s": compile_s, "compiles_in_window": in_window,
                "reference_s": time.perf_counter() - t_ref, "e2e": e2e,
                "checked_tokens": numbers.get("checked_tokens", 0),
                "sequences": seqs, "n_prompt": [len(r["prompt"]) for r in picked]},
    }
