#!/usr/bin/env python3
"""One whole traced step of an expert cell, by category, from a dumped trace.

    BENCH_DUMP_TRACE=chiprun_out/t.json.gz python3 benchmark/run.py --workload <cell> --seed <n> --seconds 40 --trace 1
    python tools/read_step.py chiprun_out/t.json.gz lfm2|kimi|moonlight [operations shown a category] [--rest]

(the dump keeps 0.7 s after the first device operation: a step of 0.5-0.9 s wants
`harness.dump_trace(keep_s=)` raised by a wrapper.) A top-level operation of the
first whole `_step_fn` module is told by its kernel's name, else by the experts'
buffer shapes or an operand that is a row mover's output, else by the pairs'
shapes: the rule PERF.md section 5's expert rows were read by (PR 34)."""
import gzip, json, re, sys
from collections import defaultdict
d = json.load(gzip.open(sys.argv[1]))
arch = sys.argv[2] if len(sys.argv) > 2 else "lfm2"
dev = d["devices"]["/device:TPU:0"]
mods = [m for m in dev["modules"] if "_step_fn" in m[0]]
ops = sorted(dev["ops"], key=lambda e: (e[1], -e[2]))
hi_all = max(e[1] + e[2] for e in ops)
whole = [m for m in mods if m[1] + m[2] <= hi_all and m[1] >= ops[0][1]]
print("step modules in the dump:", [(round(m[2] / 1e6, 2)) for m in mods], "whole:", len(whole))
m = whole[0]
lo, hi = m[1], m[1] + m[2]
inside = [e for e in ops if e[1] >= lo and e[1] + e[2] <= hi + 1]
top, end = [], -1
for e in inside:                      # top level: not inside an earlier operation (a while's body)
    if e[1] >= end:
        top.append(e); end = e[1] + e[2]
print(f"step {m[2] / 1e6:.2f} ms, {len(inside)} operations, {len(top)} top-level, busy {sum(e[2] for e in top) / 1e6:.2f} ms")
if arch == "lfm2":
    rows_shapes = [r"\[49152,2048\]", r"\[50176,2048\]", r"\[50176,1536\]", r"\[49152,1536\]"]
    idx_shapes = [r"\[98304", r"\[24576,4\]", r"\[24576,64\]", r"\[49152\]", r"\[50176\]", r"\[24576,4,", r"\[50176,1\]", r"\[768\]", r"\[392\]", r"\[9\]"]
elif arch == "moonlight":
    rows_shapes = [r"\[73728,2048\]", r"\[74752,2048\]", r"\[74752,1408\]", r"\[73728,1408\]"]
    idx_shapes = [r"\[147456", r"\[24576,6\]", r"\[24576,64\]", r"\[73728\]", r"\[74752\]", r"\[24576,6,", r"\[74752,1\]", r"\[1152\]", r"\[584\]", r"\[9\]"]
else:
    rows_shapes = [r"\[16384,2304\]", r"\[17408,2304\]", r"\[17408,1024\]", r"\[16384,1024\]"]
    idx_shapes = [r"\[131072", r"\[16384,8\]", r"\[16384,256\]", r"\[16384\]\{", r"\[17408\]", r"\[17408,1\]", r"\[1024\]\{", r"\[136\]", r"\[9\]"]
def cat(name):
    head = name.split(" = ")[0]
    if head.startswith("%moe_rows_gather"): return "layout: kernel moe_rows_gather"
    if head.startswith("%moe_rows_combine"): return "layout: kernel moe_rows_combine"
    if head.startswith("%grouped_matmul"): return "grouped products (kernels)"
    if re.match(r"%(flash_|ce_stats|kda_)", head): return "other named kernels"
    if any(re.search(s, name) for s in rows_shapes) or re.search(r"%moe_rows_(gather|combine)", name.split(" = ", 1)[-1]):
        return "layout: XLA's operations at the buffer's shapes or on the movers' outputs"
    if any(re.search(s, name) for s in idx_shapes): return "router and index work"
    if arch == "moonlight":     # what else the step is made of, by the shapes only it has
        if re.search(r"\[3,8192,16,(192|64|128|256)\]|\[3,8192,1,64\]|\[3,8192,(1,|16,)?32(,2)?\]|\[48,8192,|\[3,16,8192,|8192,3072\]|8192,576\]|8192,4096\]|\[2048,3072\]|\[2048,576\]|\[512,4096\]", name):
            return "latent attention around flash: projections, rotation, broadcast, concatenation, layout"
        if re.search(r"11264", name): return "dense feed-forward"
        if re.search(r"2816", name): return "shared experts"
        if re.search(r"20480", name): return "head and loss outside ce_stats"
    return "rest"
tot, cnt, ex = defaultdict(float), defaultdict(int), defaultdict(list)
for e in top:
    c = cat(e[0]); tot[c] += e[2]; cnt[c] += 1; ex[c].append(e)
for c in sorted(tot, key=lambda c: -tot[c]):
    print(f"{tot[c] / 1e6:9.2f} ms {cnt[c]:5d} ops  {c}")
    if c != "rest" or "--rest" in sys.argv:
        for e in sorted(ex[c], key=lambda e: -e[2])[:int(sys.argv[3]) if len(sys.argv) > 3 else 6]:
            print(f"      {e[2] / 1e3:8.1f} us  {e[0][:230]}")
