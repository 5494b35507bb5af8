#!/usr/bin/env python3
"""Read, on the chip and in one process, what a cell's limits are set from.

    python3 benchmark/limits.py --workload <cell> --seeds 1,2,... --controls 3 --seconds 1

For every seed: the cell's own run (the timed path at the timed sizes, a
short window) and its numbers against the reference: the lower readings. For
the first `--controls` seeds also the control (the reference in float8 put
in the program's place) and, for a training cell, the planted faults (half
of the batch left out; the exchange between data-parallel chips left out
reads the same): the upper readings. One JSON line a seed on stdout. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--extra", choices=("control", "fault", "both"), default="both",
                    help="a training cell's float32 reference, its control and its "
                         "fault do not fit into one process on a 16 GB chip: one each")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import check, harness, reference

    cell = harness.load_cell(a.workload, a.rehearsal)
    device = harness.open_device(cell["chips"], a.rehearsal)
    meter = harness.CompileMeter()
    kind = importlib.import_module(f"benchmark.kinds.{cell['kind']}")
    cfg, dtype = cell["model"], cell["model"]["dtype"]
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        args = types.SimpleNamespace(seed=seed, seconds=a.seconds, trace=0)
        t0 = time.perf_counter()
        with harness.interpret_kernels(a.rehearsal):
            out = kind.run(cell, args, device, meter, t0)
        line = {"seed": seed, "correct": out["correct"],
                "program": {k: c["value"] for k, c in out["checks"].items()},
                "end_to_end": out["end_to_end"], "run_s": time.perf_counter() - t0}
        gc.collect()
        jax.clear_caches()
        if i < a.controls and cell["kind"] == "train":
            ref, rows = out["run"]["ref"], cell["mix"]["rows"]
            batches = [(b[:, :-1], b[:, 1:]) for b in out["run"]["check_batches"]]
            lr = cell["train"]["learning_rate"]
            if a.extra in ("control", "both"):
                ctl = reference.train_steps(cfg, seed, batches, lr, mm=reference.mm_fp8,
                                            param_dtype=dtype)
                line["control_fp8"] = check.train_numbers(ctl, ref)
                gc.collect()
                jax.clear_caches()
            if a.extra in ("fault", "both"):
                half = reference.train_steps(cfg, seed, batches, lr, param_dtype=dtype,
                                             rows=slice(0, max(rows // 2, 1)))
                line["fault_half_batch"] = check.train_numbers(half, ref)
            line["ref_losses"] = ref["losses"]
        if i < a.controls and cell["kind"] == "serve":
            seqs, n_prompt = out["run"]["sequences"], out["run"]["n_prompt"]
            gaps = reference.served_logit_gaps(
                cfg, seed, seqs, n_prompt, mm_names=("f32", "fp8"), param_dtype=dtype,
                pad_to=cell["engine"]["max_seq_len"])
            worst = [float(g.max()) for g in gaps["fp8"]]
            line["control_fp8"] = {"logit_gap": max(worst), "per_request": worst,
                                   "median_gap": float(np.median(np.concatenate(gaps["fp8"])))}
            line["program_per_request"] = [float(g.max()) for g in gaps["served"]]
        print(json.dumps(line), flush=True)
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
