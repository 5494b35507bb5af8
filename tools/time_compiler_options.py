#!/usr/bin/env python3
"""A cell's step with and without a set of XLA compiler options, timed in
alternating windows of one process, with no float32 reference.

    python3 tools/time_compiler_options.py --workload train_mistral7b_seq4k_dp2mp2 \\
        --seed <n> --options '{"<xla option>": "<value>"}' --seconds 6 --pairs 3 \\
        --out <file>.json [--rehearsal]

One model and one optimizer state, built as the benchmark builds them: the
step is compiled with no option ("none"), its lowering compiled again with
`--options` ("options"), and the two executables take turns on the same
state (none options, options none, ...). Each window starts after the
other's last step has finished and reads the loss every `log_every` steps,
as the benchmark's window does. Then `--trace-steps` steps of each are
traced and their transfers read by instruction: a `collective-permute-done`,
an all-gather / all-reduce / reduce-scatter / all-to-all, and the TPU's
reduce-scatter fusion (`calls=%all-reduce-scatter`) are transfers; a `while`
loop's own event is neither (the products and copies inside it are events
of their own); everything else is compute. A transfer's time outside every
compute event is exposed (on a v5e the ops line holds one event at a time,
so that is all of it; a transfer that runs beside a product inside one
fusion is not an event of its own and is not seen). Writes the numbers to
`--out` as it goes; `--parent` times and reads the tree's step alone. On a
CPU (`--rehearsal`: tiny sizes, the cell's devices virtual) the options are
not given and nothing is a measurement."""
import argparse
import json
import os
import re
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _opcode(name: str) -> str:
    from paddle_tpu.observability import scopes

    m = re.match(r"%\S+ = (.*)", name, re.S)
    return scopes._details(m.group(1))[0] if m else ""


_WRAPS = re.compile(r"calls=%(all-reduce-scatter|reduce-scatter|all-gather|all-reduce"
                    r"|collective-permute|all-to-all)")


def read_transfers(events: dict) -> dict:
    """Transfer and exposed transfer seconds by kind, averaged over the device
    planes, over the whole trace; steps = the step program's runs."""
    from benchmark import reduce

    planes = sorted(events["devices"]) or ["none"]
    total, exposed, busy, runs = {}, {}, 0.0, 0.0
    for plane in planes:
        kinds, compute = {}, []
        for n, s, d in events["devices"].get(plane, {}).get("ops", []):
            op = _opcode(n)
            if op == "while":
                continue
            wraps = _WRAPS.search(n) if op in ("fusion", "async-start", "async-done") else None
            if wraps:           # the TPU's reduce-scatter fusion, an async wrapper
                op = f"{op}:{wraps.group(1)}"
            if wraps or reduce.is_collective(n) or op.startswith(reduce.COLLECTIVES):
                kinds.setdefault(op, []).append((s, s + d))
            else:
                compute.append((s, s + d))
        compute = reduce._union(compute)
        busy += reduce._length(reduce._union(compute + [iv for v in kinds.values() for iv in v]))
        for op, ivs in kinds.items():
            u = reduce._union(ivs)
            total[op] = total.get(op, 0.0) + reduce._length(u) / 1e9 / len(planes)
            exposed[op] = exposed.get(op, 0.0) + reduce._minus(u, compute) / 1e9 / len(planes)
        mods = events["devices"].get(plane, {}).get("modules", [])
        step = [m for m in mods if "step_fn" in m[0]]
        runs += len(step) / len(planes)
    return {"steps": runs, "busy_s": busy / 1e9 / max(len(planes), 1),
            "transfer_s": total, "exposed_s": exposed,
            "exposed_ms_per_step": sum(exposed.values()) * 1e3 / max(runs, 1)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--trace-steps", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--options", help="the option set as JSON")
    ap.add_argument("--parent", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    from benchmark import harness

    with harness.interpret_kernels(args.rehearsal):
        return run(args)


def run(args) -> int:
    import glob

    import jax

    from benchmark import harness, reduce, traffic
    from benchmark.kinds import train

    cell = harness.load_cell(args.workload, args.rehearsal)
    device = harness.open_device(cell["chips"], args.rehearsal)
    mix, every = cell["mix"], cell["train"]["log_every"]
    tokens = mix["rows"] * mix["seq_len"]
    out = {"workload": args.workload, "seed": args.seed, "device": device["kind"],
           "parent": args.parent, "windows": [], "traces": {}}

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    if not args.parent and not args.options:
        raise SystemExit("no option set: give --options, or --parent")
    options = None if args.parent else json.loads(args.options)
    model, opt, step = train.build(cell, args.seed, device)
    batches = traffic.token_batches(mix, args.seed, 8, cell["model"]["vocab_size"])
    feed = [train._feed(b) for b in batches]
    t0 = time.perf_counter()
    float(step(*feed[0], feed[0][1]))              # builds and compiles
    out["first_step_s.none"] = time.perf_counter() - t0
    jitted = {"none": step._jitted}
    if options is not None:
        t0 = time.perf_counter()
        jitted["options"] = step._lowered.compile(
            compiler_options=None if args.rehearsal else options)
        out["compile_s.options"] = time.perf_counter() - t0
    out["options"] = options
    harness.log(f"first step {out['first_step_s.none']:.1f}s, the options' compile "
                f"{out.get('compile_s.options', 0):.1f}s: {options}")
    save()

    def window(name, seconds):
        step._jitted = jitted[name]
        float(step(*feed[2], feed[2][1]))           # the other's steps are done
        n, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ids, labels = feed[n % len(feed)]
            loss = step(ids, labels, labels)
            n += 1
            if n % every == 0:
                float(loss)
        float(loss)
        dt = time.perf_counter() - t0
        return n, dt

    order = list(jitted)
    for k in range(args.pairs):
        for name in order if k % 2 == 0 else order[::-1]:
            n, dt = window(name, args.seconds)
            rate = n * tokens / dt / cell["chips"]
            out["windows"].append({"variant": name, "steps": n, "seconds": dt,
                                   "tokens_per_s_per_chip": rate, "ms_per_step": dt / n * 1e3})
            harness.log(f"{name}: {n} steps in {dt:.2f}s, {rate:.1f} tokens/s/chip, {dt / n * 1e3:.2f} ms a step")
            save()

    for name in order:
        step._jitted = jitted[name]
        float(step(*feed[2], feed[2][1]))
        d = os.path.join(ROOT, ".bench_trace", name)
        jax.profiler.start_trace(d)
        for t in range(args.trace_steps):
            loss = step(*feed[t % len(feed)], feed[t % len(feed)][1])
        float(loss)
        jax.profiler.stop_trace()
        path = max(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
        events = reduce.load_xplane(path)
        got = read_transfers(events)
        red = reduce.reduce_events(events, cell["chips"])
        got["reduce_py_collective_s"] = red["collective_s"]
        got["reduce_py_exposed_collective_s"] = red["exposed_collective_s"]
        got["modules_s"] = red["module_s"]
        # the longest instructions of the first device, whole events, by head
        ops = {}
        for plane in sorted(events["devices"])[:1]:
            for n, s, dur in events["devices"][plane]["ops"]:
                key = n.split(", metadata")[0][:200]
                c = ops.setdefault(key, [0, 0.0])
                c[0] += 1
                c[1] += dur / 1e9
        got["top_ops"] = sorted(([k, c, s] for k, (c, s) in ops.items()), key=lambda r: -r[2])[:60]
        out["traces"][name] = got
        harness.log(f"{name}: exposed transfers {got['exposed_ms_per_step']:.2f} ms a step "
                    f"over {got['steps']:.2f} steps: {got['exposed_s']}")
        save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
