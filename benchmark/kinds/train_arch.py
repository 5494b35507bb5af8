"""A training cell of an architecture that brings its own files: the window
drives `CompiledTrainStep.__call__` as `kinds/train.py`'s does, with the
same set-up, checked steps, window, drain and rules of `correct`; the model
builder, the leaf specs, the reference and the required work come from
`benchmark/arch/<arch>/` (`weights`, `reference`, `roofline`), `<arch>` named
by the configuration. The step collects its telemetry (`collect_metrics`):
that is where a model with held experts counts the token-expert pairs routed
to them, read with the loss. `train.warmup_steps` makes the learning rate
rise linearly to `learning_rate` over that many steps, as a pre-training job
starts; the scheduler is stepped before every call."""
from __future__ import annotations

import gc
import importlib
import json
import os
import time
import types

import numpy as np

from benchmark import check, harness, traffic
from benchmark.kinds.train import _change_norms, _feed, _state_norms


def arch_of(cell: dict, part: str):
    return importlib.import_module(f"benchmark.arch.{cell['model']['arch']}.{part}")


def build(cell: dict, seed: int, device: dict):
    import paddle_tpu as paddle
    from paddle_tpu.parallel import CompiledTrainStep

    train = cell["train"]
    model = arch_of(cell, "weights").seeded_model(cell["model"], seed)
    model.train()
    lr = train["learning_rate"]
    if train.get("warmup_steps"):
        lr = paddle.optimizer.lr.LinearWarmup(lr, train["warmup_steps"], 0.0, lr)
    opt = paddle.optimizer.AdamW(learning_rate=lr, parameters=model.parameters(),
                                 weight_decay=train["weight_decay"], multi_precision=True)
    step = CompiledTrainStep(model, lambda out, lab: out, optimizer=opt,
                             collect_metrics=True)
    return model, opt, step


def _advance(opt):
    """The next step's learning rate, where the cell has a schedule."""
    sched = getattr(opt, "_lr", None)
    if hasattr(sched, "step"):
        sched.step()


def _every_leaf(step):
    """The step as `kinds/train.py`'s helpers read it, a state for EVERY
    leaf: a frozen one (a router's weights, its correction bias) has no
    moments, a first gradient of zero and its value as its master."""
    import jax.numpy as jnp

    states = [st or {"m": jnp.zeros((), jnp.float32), "master": v.astype(jnp.float32)}
              for st, v in zip(step._opt_states, step._param_vals)]
    return types.SimpleNamespace(_opt_states=states)


def _resolutions() -> dict:
    """What the program resolved at trace time and the readers divide by: the
    scan kernels' block of heads, the rows laid out for the held experts."""
    from paddle_tpu.tuning.blocks import last_resolution

    out = {}
    for name in ("kda", "held_experts"):
        res = last_resolution(name)
        if res is not None:
            out[name] = {**res.values, **res.derived}
    return out


def _moe(step) -> dict:
    return dict(step.host_counters().get("moe") or {})


def run(cell: dict, args, device: dict, meter, t_start: float) -> dict:
    import jax

    model_cfg, mix, train = cell["model"], cell["mix"], cell["train"]
    chips = cell["chips"]
    tokens_per_step = mix["rows"] * mix["seq_len"]
    specs = arch_of(cell, "weights").leaf_specs(model_cfg)

    # ---- set-up: ONE object, driven through its first steps, then timed ----
    model, opt, step = build(cell, args.seed, device)
    batches = traffic.token_batches(mix, args.seed, train["batches"], model_cfg["vocab_size"])
    first = {"losses": []}
    n_check = train["check_steps"]
    for t in range(n_check):
        ids, labels = _feed(batches[t])
        _advance(opt)
        with harness.annotate("train.step"):
            loss = step(ids, labels, labels)
        first["losses"].append(float(loss))
        if t == 0:
            first["grad_norms"] = _state_norms(_every_leaf(step), "m") / (1.0 - 0.9)
    first["change_norms"] = np.where(
        arch_of(cell, "weights").frozen(specs), 0.0,
        _change_norms(_every_leaf(step), args.seed, specs, model_cfg["dtype"]))
    ids, labels = _feed(batches[n_check])
    _advance(opt)
    float(step(ids, labels, labels))
    step.drain()
    moe_before = _moe(step)
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.1f}s, compile {meter.secs:.1f}s, cache hits {meter.hits} "
                f"misses {meter.misses}, first losses {first['losses']}")
    traces_before, compile_s = meter.traces, meter.secs

    # ---- the measured window ----------------------------------------------
    t0 = time.perf_counter()
    profile = harness.Profile(bool(args.trace), t0, args.seconds, cell.get("trace_s", 3.0))
    n, loss, marks = 0, None, {}
    while True:
        now = time.perf_counter()
        if now - t0 >= args.seconds:
            break
        state = profile.state
        profile.poll(now)
        if profile.state != state:          # the traced slice opened or closed
            marks[profile.state] = _moe(step)
        ids, labels = _feed(batches[(n_check + 1 + n) % len(batches)])
        _advance(opt)
        with harness.annotate("train.step"):
            loss = step(ids, labels, labels)
        n += 1
        if n % train["log_every"] == 0:
            with harness.annotate("train.read_loss"):
                last_loss = float(loss)     # as a trainer logs it
    profile.stop()
    marks.setdefault("stopping", _moe(step))
    last_loss = float(loss)
    window_s = time.perf_counter() - t0
    step.drain()                            # the last steps' telemetry, after the clock
    moe = {k: v - moe_before.get(k, 0) for k, v in _moe(step).items()}
    # the same sums over the steps settled while the traced slice was open
    # (a step settles a little after it is dispatched, at both ends alike)
    moe_slice = {k: v - marks.get("running", marks["stopping"]).get(k, 0)
                 for k, v in marks["stopping"].items()}
    in_window = meter.traces - traces_before
    resolutions = _resolutions()
    peak = harness.memory_peak(device["used"])
    harness.log(f"window {window_s:.2f}s, {n} steps, {n * tokens_per_step / window_s / chips:.1f} "
                f"tokens/s/chip, peak {peak / 2**30:.2f} GiB, compiles in window {in_window}, "
                f"moe {moe}")

    # ---- free the program, then the reference ------------------------------
    del model, opt, step, loss
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = arch_of(cell, "reference").train_steps(
        model_cfg, args.seed, [(b[:, :-1], b[:, 1:]) for b in batches[:n_check]],
        train["learning_rate"], param_dtype=model_cfg["dtype"], decay=train["weight_decay"],
        warmup_steps=train.get("warmup_steps", 0))
    numbers = check.train_numbers(first, ref)
    if os.environ.get("BENCH_DUMP_REF"):
        # for `arch/<arch>/limits.py`: the control and the faults are read
        # against this run's reference, which is then not made again
        os.makedirs(os.path.dirname(os.environ["BENCH_DUMP_REF"]) or ".", exist_ok=True)
        with open(os.environ["BENCH_DUMP_REF"], "w") as f:
            json.dump({k: np.asarray(ref[k]).tolist()
                       for k in ("losses", "grad_norms", "change_norms")}, f)
    harness.log(f"reference {time.perf_counter() - t_ref:.1f}s, losses {ref['losses']}")
    numbers["compiles_in_window"] = in_window
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0
    numbers["moe_dropped"] = moe.get("dropped", 0.0)
    checks = check.judge(numbers, cell["limits"])

    rate = n * tokens_per_step / window_s / chips
    return {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": n, "failed": 0,
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s_per_chip": rate},
        "checks": checks, "memory_peak_bytes": peak, "profile": profile,
        "run": {"cell": cell, "device": device, "window_s": window_s, "steps": n,
                "tokens_per_step": tokens_per_step, "tokens_per_s_per_chip": rate,
                "compile_s": compile_s, "compiles_in_window": in_window, "moe": moe,
                "moe_slice": moe_slice, "resolutions": resolutions,
                "reference_s": time.perf_counter() - t_ref, "first": first, "ref": ref,
                "check_batches": batches[:n_check]},
    }
