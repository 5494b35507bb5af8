#!/usr/bin/env python3
"""On-chip smoke: train a few steps, hand the weights to the serving engine,
answer a few HTTP requests — at LLaMA-2-7B width, on the real TPU.

    python3 chip_smoke.py              # the real thing; needs a TPU
    python3 chip_smoke.py --rehearsal  # tiny sizes on whatever backend there
                                       # is; a control-flow check, labelled so

One process, default flags, through the entry points a user calls
(`paddle.set_device`, `CompiledTrainStep.__call__`, `ServingEngine` +
`serve_http`, `build_mesh`). Phases, in order:

  device  set_device('tpu'); platform, kind, count, versions, compile cache.
          No TPU -> one line on stderr, exit 2, nothing else runs.
  train   LlamaForCausalLM at hidden 4096 / 32 heads / vocab 32000 / seq 4096,
          bf16 + AdamW(multi_precision), the deepest stack that fits 16 GB
          without remat (3 layers; 4 need 16.23 of 15.75 GiB by XLA's own
          analysis), 4 steps on a repeated batch: losses finite and falling,
          flash fwd/bwd and the fused-CE stats kernel in the step program as
          tpu_custom_call, no [tokens, vocab] logits, one trace.
  serve   free the optimizer, sync_params_to_model, ServingEngine with a
          GB-sized KV pool, warm-up, then 4 concurrent streamed POST /generate
          over prompts of several prefill chunks + /healthz + /stats: streams
          complete, equal engine.generate(), paged kernel in the decode
          program, zero decode retraces, threads gone.
  parity  flash fwd+bwd, fused-CE stats and paged decode against their
          in-repo jnp references at these shapes, tier-1's bf16 tolerances.
  mesh    with >= 4 devices: the same train path under dp=2 x mp=2
          (build_mesh + CompiledTrainStep(mesh=)); first loss matches one
          chip, every device holds its shard, collectives in the program.

Any failed check raises: the run exits non-zero and prints no result line.
The last two stdout lines of a pass are a JSON summary (set-up facts and
seconds per phase — not performance claims) and the result object
{"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import http.client
import importlib.metadata
import json
import sys
import threading
import time

import numpy as np

# tier-1's bf16 tolerances (tests/test_fused_cross_entropy.py `_tol`,
# tests/test_paged_attention.py). Flash takes the general bf16 pair, not
# test_sequence_packing's atol=1e-3: that test compares against fp32 math,
# while sdpa's XLA path rounds its scores to bf16 before the softmax.
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)
TOL_PAGED = dict(rtol=1e-2, atol=1e-2)


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    intermediate: int
    heads: int
    vocab: int
    seq: int
    layers: int
    prompt_lens: tuple      # HTTP set: each spans more than one prefill chunk
    short_lens: tuple       # ride the warm-up as ONE packed prefill frame
    new_tokens: int
    kv_budget_mb: int       # 0 -> the serving_hbm_budget_mb flag default


# One chip: 869 M params x 14 B (bf16 + fp32 master/m/v) = 11.3 GiB of state
# and 2.2 GiB of step temporaries. Serving: 4 GiB of KV pages (the decode
# program holds ~1.2x the pool in temporaries next to it).
REAL = Sizes(hidden=4096, intermediate=11008, heads=32, vocab=32000, seq=4096,
             layers=3, prompt_lens=(300, 700, 1100, 1500),
             short_lens=(40, 90), new_tokens=32, kv_budget_mb=4096)
TINY = Sizes(hidden=64, intermediate=128, heads=4, vocab=256, seq=128,
             layers=2, prompt_lens=(20, 33, 47, 60), short_lens=(5, 9),
             new_tokens=8, kv_budget_mb=0)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def run_phase(summary, name, fn, *args):
    """Run one phase and record its facts with wall and compile seconds
    (JAX's own account of compiling: the program's compile log). No catch: a
    phase that raises ends the run."""
    from paddle_tpu.core.compile_cache import compile_totals

    log(f"== {name}")
    t0, c0 = time.perf_counter(), compile_totals()["secs"]
    facts = fn(*args)
    facts["wall_s"] = round(time.perf_counter() - t0, 1)
    facts["compile_s"] = round(compile_totals()["secs"] - c0, 1)
    summary["phases"][name] = facts
    log(f"   {name}: {json.dumps(facts)}")
    return facts


def bytes_in_use(device):
    stats = device.memory_stats()
    return None if stats is None else int(stats["bytes_in_use"])


def llama_config(sz: Sizes):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                       intermediate_size=sz.intermediate,
                       num_hidden_layers=sz.layers,
                       num_attention_heads=sz.heads,
                       num_key_value_heads=sz.heads,
                       max_position_embeddings=sz.seq)


def build_train_step(sz: Sizes, mesh=None):
    """Model + optimizer + compiled step from seed 0 — the same weights on
    one chip and on the mesh."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.parallel import CompiledTrainStep

    paddle.seed(0)
    model = LlamaForCausalLM(llama_config(sz))
    model.to(dtype="bfloat16")
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    # model(ids, labels) returns the fused head+CE loss itself
    step = CompiledTrainStep(model, lambda out, lab: out, optimizer=opt,
                             mesh=mesh)
    return model, opt, step


def train_batch(sz: Sizes, rows: int):
    """`rows` copies of ONE random row with next-token labels."""
    import paddle_tpu as paddle

    row = np.random.RandomState(0).randint(0, sz.vocab, sz.seq + 1)
    ids = np.tile(row[None, :-1], (rows, 1)).astype(np.int32)
    labels = np.tile(row[None, 1:], (rows, 1)).astype(np.int32)
    return paddle.to_tensor(ids), paddle.to_tensor(labels)


def step_program_text(step) -> str:
    return step._lowered.as_text()


def has_kernel(text: str, name: str) -> bool:
    """Whether a lowered program holds the Pallas kernel the program names
    `name` (`pallas_call(name=...)`, ops/pallas/_compat.kernel_name)."""
    return f'kernel_name = "{name}"' in text


TRAIN_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "ce_stats")


def has_full_logits(text: str, sz: Sizes, rows: int) -> bool:
    """A [rows, seq, vocab] tensor in any training dtype — what the unfused
    head materialises. (The flat [rows*seq, vocab] form says nothing here:
    at seq == hidden it is also the shape of the head weight.)"""
    return any(f"tensor<{rows}x{sz.seq}x{sz.vocab}x{t}>" in text
               for t in ("f32", "bf16", "f16"))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_train(sz: Sizes, on_tpu: bool, state: dict) -> dict:
    import jax

    from paddle_tpu.core.compile_cache import compile_totals

    dev = jax.devices()[0]
    model, opt, step = build_train_step(sz)
    ids, labels = train_batch(sz, 1)
    losses = [float(step(ids, labels, labels))]
    traced = compile_totals()["traces"]
    losses += [float(step(ids, labels, labels)) for _ in range(3)]
    retraces = compile_totals()["traces"] - traced
    log(f"   losses {losses}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0] and all(
        b <= a for a, b in zip(losses, losses[1:])),
        f"loss not falling on a repeated batch: {losses}")
    check(retraces == 0, f"{retraces} programs were traced after step 1")
    text = step_program_text(step)
    if on_tpu:
        check("tpu_custom_call" in text, "no tpu_custom_call in train step")
        for kernel in TRAIN_KERNELS:
            check(has_kernel(text, kernel),
                  f"{kernel} is not in the train step program: that Pallas "
                  f"kernel was routed around")
    check(not has_full_logits(text, sz, 1),
          "[tokens, vocab] logits are live in the train step program")
    n_params = int(sum(p.size for p in model.parameters()))
    trained = bytes_in_use(dev)

    # hand over to serving: weights back into the model, optimizer state gone
    step.sync_params_to_model()
    del step, opt
    gc.collect()
    state["model"] = model
    state["first_loss"] = losses[0]
    return {"layers": sz.layers, "params": n_params, "losses": losses,
            "retraces_after_step_1": retraces,
            "bytes_in_use_trained": trained,
            "bytes_in_use_after_free": bytes_in_use(dev)}


def _http_json(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        check(resp.status == 200, f"GET {path} -> {resp.status}")
        return json.loads(resp.read())
    finally:
        conn.close()


def _http_generate(port: int, prompt, new_tokens: int) -> list:
    """One streamed POST /generate; returns the ndjson events."""
    body = json.dumps({"prompt_ids": [int(t) for t in prompt],
                       "max_new_tokens": new_tokens}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json",
                      "Content-Length": str(len(body))})
        resp = conn.getresponse()
        check(resp.status == 200, f"POST /generate -> {resp.status}")
        return [json.loads(line) for line in resp if line.strip()]
    finally:
        conn.close()


def phase_serve(sz: Sizes, on_tpu: bool, state: dict) -> dict:
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = state.pop("model")
    model.eval()
    engine = ServingEngine(model, ServingConfig(
        hbm_budget_mb=sz.kv_budget_mb, max_seq_len=sz.seq))
    rng = np.random.RandomState(1)

    def draw(lens):
        return [rng.randint(1, sz.vocab, n).astype(np.int32) for n in lens]

    # warm-up, the way a deployment readies a replica: other tokens, same
    # lengths, so every program the requests below need is compiled before
    # the 60 s request deadline applies. The two short prompts arrive
    # together and ride ONE packed segment-id prefill frame.
    engine.generate(draw(sz.prompt_lens + sz.short_lens), max_new_tokens=2)
    packed = int(engine.stats()["prefill_packed_requests"])
    check(packed >= len(sz.short_lens),
          f"packed prefill did not run ({packed} packed requests)")
    b, pmax = engine.decode_batch, engine.pages_per_seq
    decode_text = engine._decode().lower(
        engine._params, engine._cache, jnp.zeros(b, jnp.int32),
        jnp.zeros(b, jnp.int32), jnp.zeros((b, pmax), jnp.int32),
        jnp.zeros((b, 2), jnp.uint32), jnp.zeros(b, jnp.float32),
        jnp.zeros(b, jnp.int32), jnp.ones(b, jnp.float32),
        None, None, None).as_text()
    if on_tpu:
        check("tpu_custom_call" in decode_text
              and has_kernel(decode_text, "paged_decode"),
              "the decode program does not hold the paged Pallas kernel")
    engine.mark_warmup()

    prompts = draw(sz.prompt_lens)
    srv = engine.serve_http(0, block=False)
    port = srv.server_address[1]
    server = threading.Thread(target=srv.serve_forever,
                              name="chip_smoke.http", daemon=True)
    server.start()
    streams: dict = {}

    def client(i):
        try:
            streams[i] = _http_generate(port, prompts[i], sz.new_tokens)
        except Exception as e:  # re-raised on the main thread below
            streams[i] = e

    try:
        health = _http_json(port, "/healthz")
        clients = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=180)
            check(not t.is_alive(), "a /generate client did not finish")
        stats = _http_json(port, "/stats")
    finally:
        engine.shutdown_http()
        server.join(timeout=10)
    check(health.get("ok") is True, f"/healthz not ok: {health}")
    http_tokens = []
    for i in range(len(prompts)):
        if isinstance(streams[i], Exception):
            raise streams[i]
        events = streams[i]
        check(events and events[-1].get("done") is True
              and events[-1].get("tokens") == sz.new_tokens,
              f"stream {i} did not end in done with {sz.new_tokens} tokens: "
              f"{events[-2:]}")
        http_tokens.append([e["token"] for e in events if "token" in e])

    # the same prompts straight through the engine: greedy streams must agree
    ref = engine.generate(prompts, max_new_tokens=sz.new_tokens)
    for i, (got, want) in enumerate(zip(http_tokens, ref)):
        check(got == [int(t) for t in want],
              f"stream {i} differs from engine.generate(): {got} vs {want}")
    retraces = engine.decode_retraces_after_warmup
    check(retraces == 0 and stats["decode_retraces_after_warmup"] == 0,
          f"decode retraced {retraces} times after warm-up")
    check(not server.is_alive(), "HTTP server thread still alive")
    left = [t.name for t in threading.enumerate()
            if t.name.startswith("paddle_tpu.serving")]
    check(not left, f"serving threads still alive: {left}")
    facts = {"requests": len(prompts), "prompt_lens": list(sz.prompt_lens),
             "new_tokens": sz.new_tokens, "kv_pages": engine.num_pages,
             "page_size": engine.page_size,
             "prefill_programs": engine.prefill_traces,
             "packed_requests": packed, "decode_retraces_after_warmup": 0,
             "streams_equal_generate": True}
    del engine, model
    gc.collect()
    return facts


def _closeness(name: str, got, ref, rtol: float, atol: float) -> dict:
    """Element-wise tier-1 tolerance, plus a relative L2 bound so tensors of
    small magnitude are not passed by `atol` alone."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} vs {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = np.abs(got - ref)
    rel_l2 = float(np.linalg.norm(got - ref)
                   / max(float(np.linalg.norm(ref)), 1e-30))
    check(bool((err <= atol + rtol * np.abs(ref)).all()) and rel_l2 <= rtol,
          f"{name}: max abs err {float(err.max()):.3e}, rel L2 {rel_l2:.3e} "
          f"outside rtol={rtol} atol={atol}")
    return {"max_abs_err": float(f"{float(err.max()):.3e}"),
            "rel_l2": float(f"{rel_l2:.3e}")}


def phase_parity(sz: Sizes, on_tpu: bool, rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.pallas import flash_attention, fused_ce
    from paddle_tpu.ops.pallas import paged_attention as paged

    # a CPU rehearsal reaches the kernels the only way a CPU can; the real
    # run never forces interpret mode
    def kernel_mode(mod):
        return mod.force_interpret() if rehearsal else contextlib.nullcontext()

    bf = jnp.bfloat16
    rng = np.random.RandomState(2)
    d = sz.hidden // sz.heads
    facts = {}

    # -- flash forward + backward vs sdpa's XLA path -------------------------
    q, k, v = (jnp.asarray(rng.randn(1, sz.seq, sz.heads, d), bf)
               for _ in range(3))
    wgt = jnp.asarray(rng.randn(1, sz.seq, sz.heads, d), jnp.float32)

    def attn(q, k, v):
        out = F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=True,
            training=False)._value
        return jnp.sum(out.astype(jnp.float32) * wgt), out

    def attn_run(use_pallas):
        # the route is read at trace time: a fresh jit per route
        set_flags({"use_pallas_attention": use_pallas})
        try:
            fn = jax.jit(jax.value_and_grad(attn, argnums=(0, 1, 2),
                                            has_aux=True))
            text = fn.lower(q, k, v).as_text()
            (_, out), grads = fn(q, k, v)
            return text, (out,) + tuple(grads)
        finally:
            set_flags({"use_pallas_attention": True})

    with kernel_mode(flash_attention):
        text, got = attn_run(True)
    if on_tpu:
        check(all(has_kernel(text, n) for n in TRAIN_KERNELS[:3]),
              "flash parity did not run the Pallas kernels")
    text, ref = attn_run(False)
    check("tpu_custom_call" not in text, "the XLA reference ran a kernel")
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
        facts[f"flash_{name}"] = _closeness(f"flash {name}", g, r, **TOL_BF16)
    del got, ref, q, k, v, wgt

    # -- fused-CE stats kernel vs the token-chunked scan ---------------------
    n = sz.seq
    x = jnp.asarray(rng.randn(n, sz.hidden), bf)
    w = jnp.asarray(rng.randn(sz.hidden, sz.vocab) * 0.02, bf)
    lab = jnp.asarray(rng.randint(0, sz.vocab, n), jnp.int32)
    cfg = fused_ce._resolve_cfg(n, sz.vocab, -100, 0.0, 0.0, 0, 0, "pallas",
                                None, True, False)
    got = jax.jit(lambda *a: fused_ce._stats_pallas(cfg, *a))(x, w, lab)
    ref = jax.jit(lambda x, w, l: fused_ce._stats_tokens(
        cfg, x, w, None, l))(x, w, lab)
    for name, g, r in zip(("max", "sumexp", "target", "sumlogits"), got, ref):
        facts[f"ce_{name}"] = _closeness(f"fused-CE {name}", g, r, **TOL_BF16)
    del got, ref, x, w

    # -- paged decode vs the jnp gather reference ----------------------------
    ps, batch = 16, 8
    pages_per_seq = sz.seq // ps
    lens = np.array([int(sz.seq * f) for f in
                     (0.07, 0.17, 0.27, 0.37, 0.01, 1.0, 0.0, 0.004)],
                    np.int32)
    lens[5] = sz.seq - 1
    table = np.zeros((batch, pages_per_seq), np.int32)   # 0 = null page
    for r in range(batch):
        used = -(-int(lens[r]) // ps)
        table[r, :used] = 1 + r * pages_per_seq + np.arange(used)
    pool = (sz.heads, 1 + batch * pages_per_seq, ps, d)
    kp, vp = (jnp.asarray(rng.randn(*pool), bf) for _ in range(2))
    qd = jnp.asarray(rng.randn(batch, sz.heads, d), bf)
    with kernel_mode(paged):
        fn = jax.jit(paged.paged_attention)
        if on_tpu:
            check(has_kernel(fn.lower(qd, kp, vp, table, lens).as_text(),
                             "paged_decode"),
                "paged parity did not run the Pallas kernel")
        got = fn(qd, kp, vp, table, lens)
    ref = jax.jit(paged.paged_attention_reference)(qd, kp, vp, table, lens)
    facts["paged_decode"] = _closeness("paged decode", got, ref, **TOL_PAGED)
    return facts


def phase_mesh(sz: Sizes, on_tpu: bool, state: dict) -> dict:
    import jax

    from paddle_tpu.distributed.mesh import build_mesh, set_mesh

    devices = jax.devices()
    if len(devices) < 4:
        why = f"{len(devices)} device(s) present, dp=2 x mp=2 needs 4"
        log(f"   mesh phase did not run: {why}")
        return {"ran": False, "why": why}
    mesh = build_mesh({"dp": 2, "mp": 2}, devices=devices[:4])
    try:
        model, opt, step = build_train_step(sz, mesh=mesh)
        # One chip cannot hold a 2-row step next to full AdamW state (15.7 of
        # 15.75 GiB), so the mesh batch is the one-chip row twice, one per dp
        # shard: its mean loss equals the one-chip loss by construction.
        ids, labels = train_batch(sz, 2)
        losses = [float(step(ids, labels, labels)) for _ in range(2)]
        log(f"   losses {losses} (one chip, first step: "
            f"{state['first_loss']})")
        check(all(np.isfinite(losses)) and losses[1] < losses[0],
              f"mesh losses not finite and falling: {losses}")
        want = state["first_loss"]
        check(abs(losses[0] - want) <= TOL_BF16["atol"]
              + TOL_BF16["rtol"] * abs(want),
              f"first loss on the mesh {losses[0]} vs one chip {want}")
        text = step_program_text(step)
        if on_tpu:
            for kernel in TRAIN_KERNELS:
                check(has_kernel(text, kernel),
                      f"{kernel} not in the mesh program")
        hlo = step._executable.as_text()
        collectives = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                       for op in ("all-reduce", "all-gather",
                                  "reduce-scatter")}
        check(sum(collectives.values()) > 0,
              "no collective in the compiled mesh program")
        # every device holds its parameter shard: mp=2 halves the matrices
        total = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in step._param_vals)
        per_dev, in_use = [], []
        for i, dev in enumerate(devices[:4]):
            held = sum(s.data.nbytes for v in step._param_vals
                       for s in v.addressable_shards if s.device == dev)
            check(0 < held <= total // 2 + (1 << 20),
                  f"device {i} holds {held} parameter bytes of {total}")
            per_dev.append(int(held))
            used = bytes_in_use(dev)
            check(used is not None or not on_tpu, "no memory_stats on TPU")
            in_use.append(used)
        if in_use[0] is not None:
            check(min(in_use) > 0 and max(in_use) < 2 * min(in_use),
                  f"device memory is not spread evenly: {in_use}")
        return {"ran": True, "axes": {"dp": 2, "mp": 2}, "losses": losses,
                "one_chip_first_loss": want, "collectives": collectives,
                "param_bytes_total": total, "param_bytes_per_device": per_dev,
                "bytes_in_use_per_device": in_use}
    finally:
        set_mesh(None)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever backend is present: checks "
                         "the script's control flow, says nothing about a chip")
    args = ap.parse_args(argv)
    sz = TINY if args.rehearsal else REAL

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core import native
    from paddle_tpu.core.compile_cache import (compile_totals,
                                               enable_compile_cache,
                                               start_compile_log)

    # -- device check first: nothing below runs on a CPU by accident ----------
    if args.rehearsal:
        log("REHEARSAL: tiny sizes, not a chip result")
        cache_dir = None   # executables of this host's CPU must not be kept
    else:
        try:
            paddle.set_device("tpu")
        except RuntimeError as e:
            print(f"chip_smoke: no TPU, nothing was run ({e})",
                  file=sys.stderr)
            return 2
        cache_dir = enable_compile_cache()
    start_compile_log()   # a rehearsal keeps no cache and still counts
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    summary = {"chip_smoke": "pass", "rehearsal": args.rehearsal,
               "device": device, "versions": versions,
               "compile_cache_dir": cache_dir,
               "native_core_loaded": native.available(), "phases": {}}
    log(f"device {json.dumps(device)} versions {json.dumps(versions)} "
        f"compile cache {cache_dir} native core "
        f"{summary['native_core_loaded']}")

    state: dict = {}
    t0 = time.perf_counter()
    run_phase(summary, "train", phase_train, sz, on_tpu, state)
    run_phase(summary, "serve", phase_serve, sz, on_tpu, state)
    run_phase(summary, "parity", phase_parity, sz, on_tpu, args.rehearsal)
    run_phase(summary, "mesh", phase_mesh, sz, on_tpu, state)
    summary["wall_s"] = round(time.perf_counter() - t0, 1)
    totals = compile_totals()
    summary["compile_s"] = round(totals["secs"], 1)
    summary["persistent_cache"] = {"hits": totals["hits"],
                                   "misses": totals["misses"]}
    summary["claim"] = None
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device} | (
        {"rehearsal": True} if args.rehearsal else {})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
