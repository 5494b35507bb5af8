#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s). Without the TPU chips the cell asks for it
exits 2 and prints no result. `--rehearsal` is the one other mode: the cell's
structure at the tiny sizes of `rehearsal.json`, on the CPU with the kernels
interpreted, its result line labelled with the CPU platform. Nothing a
rehearsal prints is a measurement.

The last line of stdout is the result object of the builder's contract:
correct, attempted, failed, metrics, device, (breakdown,) checks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness, reduce

    manifest = harness.load_manifest()
    if args.workload not in [w["name"] for w in manifest["workloads"]]:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload, args.rehearsal)
    try:
        device = harness.open_device(cell["chips"], args.rehearsal)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    meter = harness.CompileMeter()
    kind = importlib.import_module(f"benchmark.kinds.{cell['kind']}")
    with harness.interpret_kernels(args.rehearsal):
        out = kind.run(cell, args, device, meter, T_START)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = harness.cell_metrics(manifest, args.workload, group)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": None, "device": dev}
    if args.trace:
        reduced = out["profile"].reduce(cell["chips"])
        # the slice on the trace's own clock; its start and length on the host's
        # for the readers that hold it against the program's host-clock records
        run = dict(out["run"], trace=reduced, trace_window_s=reduced and reduced["window_s"],
                   trace_t0=out["profile"].t_begin,
                   trace_host_window_s=out["profile"].host_window_s, rehearsal=args.rehearsal)
        values = harness.read_per_layer([m["name"] for m in metrics], run)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            harness.log(f"slice on the trace's clock {reduced['window_s']:.9f}s, busy "
                        f"{reduced['busy_s']:.9f}s, {reduce.main_module_runs(reduced):.4f} steps; "
                        f"on the host's clock {out['profile'].host_window_s:.9f}s")
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            dump = os.environ.get("BENCH_DUMP_TRACE")
            if dump:
                keep = os.environ.get("BENCH_DUMP_TRACE_S", "0.7")
                harness.dump_trace(dump, reduced, None if keep == "all" else float(keep))
    else:
        values = out["end_to_end"]
    result["metrics"] = harness.with_units(values, metrics)
    if args.rehearsal:
        result["rehearsal"] = True
    harness.emit(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
