#!/usr/bin/env python3
"""The grouped products alone on the chip, by column tile: the table the
budget of `grouped_matmul._col_tile` (`_compat.vmem_budget`) was fixed from
(PERF.md, PR 38).

    python tools/gmm_tile_table.py [--out chiprun_out/gmm_tiles.json] [--cells moonlight,lfm2,kimi]

For each expert cell's two (k, n), at its rows laid out (8 held experts, the
pairs of a random router over all experts in the dispatcher's layout: about a
quarter of the rows), the forward kernel and the dw kernel are timed at every
column tile count from the one the rule before PR 38 cut (the cell's data
below) down to one, by forcing `_col_tile`. Each forward is compared with the
one-tile forward (a partial last tile changes no element). A tile the
compiler refuses is reported with its message. Needs a TPU: only `--cells
tiny` runs without one, in interpret mode, to rehearse the script. Every row
names the device it was timed on; the last stdout line is one JSON object."""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: (tokens a step, experts a token, experts of the layer, d_model,
# d_hidden, the column tiles the rule before PR 38 cut d -> hidden and back)
CELLS = {"moonlight": (24576, 6, 64, 2048, 1408, 11, 4),
         "lfm2": (24576, 4, 64, 2048, 1536, 3, 4),
         "kimi": (16384, 8, 256, 2304, 1024, 2, 2),
         "tiny": (256, 2, 16, 256, 384, 3, 2)}    # a CPU rehearsal, over 3 and 2 tiles


def _tiles(n: int, most: int) -> list[int]:
    """Every distinct tile from `most` tiles down to one."""
    bns = []
    for t in range(most, 0, -1):
        bn = n if t == 1 else -(-(-(-n // t)) // 128) * 128
        if bn not in bns:
            bns.append(bn)
    return bns


def _time(fn, *args, reps=3, calls=10):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--cells", default="moonlight,lfm2,kimi")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.incubate.distributed.models.moe.dropless import pair_rows
    from paddle_tpu.incubate.distributed.models.moe.held_experts import held_rows

    gm = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")
    device = jax.devices()[0]
    interpret = device.platform != "tpu"
    if interpret and args.cells != "tiny":
        # interpret-mode times would read like a chip's table
        print(f"gmm_tile_table: no TPU ({device.device_kind}); only --cells tiny runs here",
              file=sys.stderr)
        return 2
    rule, bf = gm._col_tile, jnp.bfloat16
    result = {"device_kind": device.device_kind, "rows": {}, "table": []}
    for cell in args.cells.split(","):
        tokens, top_k, experts, d, h, *old = CELLS[cell]
        rows, bm = held_rows(tokens * top_k, 8, experts)
        ids = jax.random.randint(jax.random.PRNGKey(0), (tokens * top_k,), 0, experts)
        gids = jnp.where(ids < 8, ids, 8).astype(jnp.int32)
        gbuf = pair_rows(gids, 8, bm, rows)[1]
        m = gbuf.shape[0]
        result["rows"][cell] = {"buffer_rows": m, "block_rows": bm,
                                "pairs": int(jnp.sum(gids < 8))}
        for (k, n), most in zip(((d, h), (h, d)), old):
            kx = jax.random.split(jax.random.PRNGKey(k * n), 3)
            x = jax.random.normal(kx[0], (m, k), bf)
            w = (jax.random.normal(kx[1], (8, k, n), jnp.float32) * k ** -0.5).astype(bf)
            dy = jax.random.normal(kx[2], (m, n), bf)
            ref = {}
            for bn in reversed(_tiles(n, most)):       # one tile first: the reference
                gm._col_tile = lambda *_a, bn=bn: bn   # read when a call is traced
                fwd = jax.jit(lambda x, w, g: gm._gmm_fwd_pallas(x, w, g, bm, interpret, 1))
                dw = jax.jit(lambda x, dy, g: gm._gmm_dw_pallas(x, dy, g, 8, bm, interpret, 1))
                row = {"device_kind": device.device_kind, "cell": cell, "k": k, "n": n,
                       "bn": bn, "tiles": -(-n // bn), "old_rule": -(-n // bn) == most,
                       "rule_fwd": bn == rule(bm, k, n, *gm._fwd_sizes(bm, bf, bf)),
                       "rule_dw": bn == rule(bm, k, n, *gm._dw_sizes(k, bf, bf))}
                for name, fn, b in (("fwd", fwd, w), ("dw", dw, dy)):
                    try:
                        row[f"{name}_ms"] = round(_time(fn, x, b, gbuf), 4)
                        y = np.asarray(fn(x, b, gbuf))
                        row[f"{name}_max_diff"] = float(np.max(np.abs(y - ref.setdefault(name, y))))
                    except Exception as e:  # the compiler's refusal IS the result
                        row[f"{name}_error"] = f"{type(e).__name__}: {e}"[:400]
                gm._col_tile = rule
                result["table"].append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
