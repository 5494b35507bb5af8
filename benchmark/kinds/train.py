"""A training cell: the window drives `CompiledTrainStep.__call__`."""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import check, harness, reference, traffic, weights


def build(cell: dict, seed: int, device: dict):
    """The program as a user builds it: model in the configuration's type,
    the seed's weights in place of its own, AdamW with float32 masters, the
    compiled step (on the cell's mesh where it has one)."""
    import paddle_tpu as paddle
    from paddle_tpu.parallel import CompiledTrainStep

    model_cfg, train = cell["model"], cell["train"]
    mesh = None
    if cell.get("mesh"):
        from paddle_tpu.distributed.mesh import build_mesh

        mesh = build_mesh(dict(cell["mesh"]), devices=device["used"])
    model = harness.seeded_model(model_cfg, seed)
    params = model.parameters()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=train["learning_rate"],
                                 parameters=params, weight_decay=train["weight_decay"],
                                 multi_precision=True)
    step = CompiledTrainStep(model, lambda out, lab: out, optimizer=opt, mesh=mesh)
    return model, opt, step


def _feed(batch):
    """One batch the way chip_smoke.py feeds it: host arrays to tensors."""
    import paddle_tpu as paddle

    ids, labels = batch[:, :-1], batch[:, 1:]
    return paddle.to_tensor(np.ascontiguousarray(ids)), paddle.to_tensor(np.ascontiguousarray(labels))


def _state_norms(step, which: str) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda xs: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                       for x in xs]))
    return np.asarray(fn([st[which] for st in step._opt_states]))


def _change_norms(step, seed: int, specs, dtype) -> np.ndarray:
    """||master - the seed's leaf|| for every leaf, one leaf at a time: the
    leaf is made again from the seed, never kept."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(master, parts, index, mean, std):
        start = weights.make_leaf(weights.key_of(parts), index, master.shape, mean, std, dtype)
        return jnp.sqrt(jnp.sum(jnp.square(master - start.astype(jnp.float32))))

    parts = weights.seed_parts(seed)
    return np.array([float(one(st["master"], parts, i, mean, std))
                     for i, (st, (_, _, mean, std)) in enumerate(zip(step._opt_states, specs))])


def run(cell: dict, args, device: dict, meter, t_start: float) -> dict:
    import jax

    model_cfg, mix, train = cell["model"], cell["mix"], cell["train"]
    chips = cell["chips"]
    rows, seq = mix["rows"], mix["seq_len"]
    tokens_per_step = rows * seq
    specs = weights.leaf_specs(model_cfg)

    # ---- set-up: ONE object, driven through its first steps, then timed ----
    model, opt, step = build(cell, args.seed, device)
    batches = traffic.token_batches(mix, args.seed, train["batches"], model_cfg["vocab_size"])
    first = {"losses": []}
    n_check = train["check_steps"]
    for t in range(n_check):
        ids, labels = _feed(batches[t])
        with harness.annotate("train.step"):
            loss = step(ids, labels, labels)
        first["losses"].append(float(loss))
        if t == 0:
            # the first gradient as the optimizer got it: m_1 = (1 - b1) g_1
            first["grad_norms"] = _state_norms(step, "m") / (1.0 - 0.9)
    first["change_norms"] = _change_norms(step, args.seed, specs, model_cfg["dtype"])
    ids, labels = _feed(batches[n_check])
    float(step(ids, labels, labels))
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.1f}s, compile {meter.secs:.1f}s, cache hits {getattr(meter, 'hits', 0)} "
                f"misses {getattr(meter, 'misses', 0)}, first losses {first['losses']}")
    traces_before, compile_s = meter.traces, meter.secs

    # ---- the measured window ----------------------------------------------
    t0 = time.perf_counter()
    profile = harness.Profile(bool(args.trace), t0, args.seconds, cell.get("trace_s", 3.0))
    n, loss = 0, None
    while True:
        now = time.perf_counter()
        if now - t0 >= args.seconds:
            break
        profile.poll(now)
        ids, labels = _feed(batches[(n_check + 1 + n) % len(batches)])
        with harness.annotate("train.step"):
            loss = step(ids, labels, labels)
        n += 1
        if n % train["log_every"] == 0:
            with harness.annotate("train.read_loss"):
                last_loss = float(loss)     # as a trainer logs it
    profile.stop()
    last_loss = float(loss)
    window_s = time.perf_counter() - t0
    in_window = meter.traces - traces_before
    peak = harness.memory_peak(device["used"])
    harness.log(f"window {window_s:.2f}s, {n} steps, {n * tokens_per_step / window_s / chips:.1f} "
                f"tokens/s/chip, peak {peak / 2**30:.2f} GiB, compiles in window {in_window}")

    # ---- free the program, then the reference ------------------------------
    from paddle_tpu.distributed.mesh import set_mesh

    del model, opt, step, loss
    set_mesh(None)
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference.train_steps(
        model_cfg, args.seed,
        [(b[:, :-1], b[:, 1:]) for b in batches[:n_check]],
        train["learning_rate"], param_dtype=model_cfg["dtype"])
    numbers = check.train_numbers(first, ref)
    harness.log(f"reference {time.perf_counter() - t_ref:.1f}s, losses {ref['losses']}")
    numbers["compiles_in_window"] = in_window
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0
    checks = check.judge(numbers, cell["limits"])

    rate = n * tokens_per_step / window_s / chips
    return {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": n, "failed": 0,
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s_per_chip": rate},
        "checks": checks, "memory_peak_bytes": peak, "profile": profile,
        "run": {"cell": cell, "device": device, "window_s": window_s, "steps": n,
                "tokens_per_step": tokens_per_step, "tokens_per_s_per_chip": rate,
                "compile_s": compile_s, "compiles_in_window": in_window,
                "reference_s": time.perf_counter() - t_ref, "first": first, "ref": ref,
                "check_batches": batches[:n_check]},
    }
