#!/usr/bin/env python3
"""Find the highest rate a serving cell sustains: once, by a sweep on the
chip. One engine, warmed once; the cell's mix offered at each rate for
`--seconds`, drained, reported. The knee is the highest rate at which the
backlog at the close does not grow with the length of the window and the
tails stay flat; the cell's file then fixes 0.8 of it.

    python3 benchmark/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args(argv)

    from benchmark import harness, traffic
    from benchmark.kinds import serve

    cell = harness.load_cell(a.workload, a.rehearsal)
    harness.open_device(cell["chips"], a.rehearsal)
    t0 = time.perf_counter()
    with harness.interpret_kernels(a.rehearsal):
        model, engine = serve.build(cell, a.seed)
        serve.warm_up(engine, cell["mix"], cell["model"]["vocab_size"])
        print(json.dumps({"setup_s": time.perf_counter() - t0, "kv_pages": engine.num_pages,
                          "prefill_programs": engine.prefill_traces}), flush=True)
        for rate in (float(r) for r in a.rates.split(",")):
            mix = dict(cell["mix"], rate_per_s=rate)
            reqs = traffic.requests(mix, a.seed, a.seconds, cell["model"]["vocab_size"])
            w = serve.Window(engine, reqs, a.seconds)
            w.run()
            close = w.t0 + a.seconds
            backlog = sum(1 for r in w.records
                          if not (r["times"] and w.done(r) and r["times"][-1] <= close))
            waits = [r["req"].admitted_t - r["req"].arrival_t for r in w.records if r["req"]]
            waits.sort()
            print(json.dumps(dict(
                w.end_to_end(), rate=rate, requests=len(reqs), unfinished_at_close=backlog,
                never_came=sum(1 for r in w.records if not w.done(r)),
                drain_s=w.t_end - close, queue_wait_p95_ms=1e3 * waits[int(0.95 * (len(waits) - 1))]
                if waits else None, evictions=sum(r["req"].evictions for r in w.records if r["req"]),
                slot_fill=engine.stats()["slot_fill"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
