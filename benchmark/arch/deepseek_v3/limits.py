#!/usr/bin/env python3
"""The upper readings a DeepSeek-V3-family training cell's limits are set from, on the
chip (`benchmark/limits.py` reads the lower ones: the program's own).

    python3 benchmark/arch/deepseek_v3/limits.py --workload <cell> --seed <n> --run control_fp8

ONE run a process: a second reference-sized program does not fit beside what
the first leaves on a 16 GB chip. `ref` is the float32 reference over the
cell's checked steps; its numbers go to
`chiprun_out/limits/<cell>_<seed>.json` (a benchmark run of that seed with
`BENCH_DUMP_REF=<that path>` leaves the same file). The other runs read it
and print their gaps against it, one JSON line: `control_fp8` (the reference
with every projection's operands rounded to float8_e4m3, the precision below
the configuration's bfloat16; the router float32), `fault_half_batch` (half
of the batch left out), `fault_other_experts` (the NEXT `n_routed_experts`
experts computed in place of the held ones) and `fault_no_rotation` (the
rotated query and key channels left as the projections gave them)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))))

RUNS = ("ref", "control_fp8", "fault_half_batch", "fault_other_experts", "fault_no_rotation")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--run", required=True, choices=RUNS)
    a = ap.parse_args(argv)

    import numpy as np

    from benchmark import check, harness, reference, traffic
    from benchmark.arch.deepseek_v3 import reference as DR

    cell = harness.load_cell(a.workload)
    harness.open_device(cell["chips"], False)
    cfg, train, mix = cell["model"], cell["train"], cell["mix"]
    batches = traffic.token_batches(mix, a.seed, train["batches"], cfg["vocab_size"])
    pairs = [(b[:, :-1], b[:, 1:]) for b in batches[:train["check_steps"]]]
    kw = {"ref": {}, "control_fp8": {"mm": reference.mm_fp8},
          "fault_half_batch": {"rows": slice(0, max(mix["rows"] // 2, 1))},
          "fault_other_experts": {"first": cfg.get("first_held_expert", 0)
                                  + cfg["n_routed_experts"]},
          "fault_no_rotation": {"rotated": False}}[a.run]
    t0 = time.perf_counter()
    out = DR.train_steps(cfg, a.seed, pairs, train["learning_rate"], param_dtype=cfg["dtype"],
                         decay=train["weight_decay"],
                         warmup_steps=train.get("warmup_steps", 0), **kw)
    line = {"seed": a.seed, "run": a.run, "losses": out["losses"],
            "seconds": time.perf_counter() - t0}
    path = os.path.join(harness.ROOT, "chiprun_out", "limits", f"{a.workload}_{a.seed}.json")
    if a.run == "ref":
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({k: np.asarray(out[k]).tolist()
                       for k in ("losses", "grad_norms", "change_norms")}, f)
    else:
        with open(path) as f:
            ref = {k: np.asarray(v) for k, v in json.load(f).items()}
        line.update(check.train_numbers(out, ref))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
