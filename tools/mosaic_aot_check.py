#!/usr/bin/env python3
"""Does Mosaic accept the Pallas kernels at 7B-width shapes? Compile-only.

    python tools/mosaic_aot_check.py            # the dense train/serve path
    python tools/mosaic_aot_check.py --extra    # + the kernels off that path,
                                                #   Kimi-Linear's and LFM2-MoE's
                                                #   among them

Each case is lowered and fully compiled for a TPU — block-shape checks,
Mosaic's own lowering, the scoped-VMEM limit — and reported as `ok` or
`refused` with the compiler's message. Nothing is executed, so it needs no
chip: on a CPU backend it compiles for a v5e through libtpu's compile-only
client (`jax.experimental.topologies`), which is how a sandbox with no
accelerator finds out what the chip would refuse before spending chip time.
On a TPU backend it compiles for the device that is there.

Exit code 0 when every dense-path case compiles (the `--extra` cases are a
record, they never fail the run), 1 when one is refused, 3 when no TPU
compiler can be reached from this process. The last stdout line is one JSON
object with every case's outcome.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _target():
    """(devices to compile for, whether they are compile-only stand-ins)."""
    import jax

    if jax.devices()[0].platform == "tpu":
        return jax.devices(), False
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return list(topo.devices), True


def _cases(extra: bool):
    """name -> (fn, [(shape, dtype), ...]); shapes of the chip_smoke model:
    hidden 4096, 32 heads x 128, vocab 32000, seq 4096, 16-token pages."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy_loss
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    from paddle_tpu.ops.pallas.rmsnorm_kernel import rmsnorm

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    s, h, d, v, hid = 4096, 32, 128, 32000, 4096
    pages, ps, batch = 2049, 16, 8

    def f32sum(x):
        return x.astype(f32).sum()

    def flash(q, k, v_, *seg):
        return flash_attention_bshd(q, k, v_, causal=True,
                                    segment_ids=seg[0] if seg else None)

    def flash_grad(q, k, v_, *seg):
        return jax.grad(lambda a, b, c: f32sum(flash(a, b, c, *seg)),
                        argnums=(0, 1, 2))(q, k, v_)

    def ce_grad(x, w, lab):
        return jax.value_and_grad(
            lambda a, b: fused_linear_cross_entropy_loss(a, b, lab).sum(),
            argnums=(0, 1))(x, w)

    qkv = [((1, s, h, d), bf)] * 3
    pool = [((h, pages, ps, d), bf)] * 2
    table = [((batch, s // ps), i32), ((batch,), i32)]
    dense = {
        "flash fwd+bwd seq 4096": (flash_grad, qkv),
        "fused-CE stats fwd+bwd [4096,4096]x[4096,32000]": (
            ce_grad, [((s, hid), bf), ((hid, v), bf), ((s,), i32)]),
        "paged decode MHA batch 8": (
            paged_decode_attention, [((batch, h, d), bf)] + pool + table),
        # the serving engine's smallest packed-prefill frame: one K block
        "segment flash fwd seq 64 (pack frame)": (
            flash, [((1, 64, h, d), bf)] * 3 + [((1, 64), i32)]),
    }
    if not extra:
        return dense, {}

    def gmm_grad(x, w, g):
        return jax.grad(lambda a, b: grouped_matmul(a, b, g).sum(),
                        argnums=(0, 1))(x, w)

    def rms_grad(x, w):
        return jax.grad(lambda a, b: f32sum(rmsnorm(a, b)),
                        argnums=(0, 1))(x, w)

    def paged_int8(q, k, v_, pt, lens, ks, vs):
        return paged_decode_attention(q, k, v_, pt, lens, k_scales=ks,
                                      v_scales=vs)

    gmm = [((8192, hid), bf), ((8, hid, 1024), bf), ((8192,), i32)]
    off_path = {
        "grouped matmul fwd 8192x4096 -> 8 x [4096,1024]": (
            grouped_matmul, gmm),
        "grouped matmul fwd+dx+dw": (gmm_grad, gmm),
        "rmsnorm fwd [4096,4096]": (rmsnorm, [((s, hid), bf), ((hid,), bf)]),
        "rmsnorm fwd+bwd [4096,4096]": (
            rms_grad, [((s, hid), bf), ((hid,), bf)]),
        "paged decode int8 pool": (
            paged_int8, [((batch, h, d), bf)]
            + [((h, pages, ps, d), jnp.int8)] * 2 + table
            + [((h, pages, ps), f32)] * 2),
        "paged verify frame [8, 4+1]": (
            paged_decode_attention,
            [((batch, 5, h, d), bf)] + pool + table),
        "paged decode GQA 32q/8kv": (
            paged_decode_attention, [((batch, h, d), bf)]
            + [((8, pages, ps, d), bf)] * 2 + table),
        "segment flash fwd+bwd seq 4096 batch 1": (
            flash_grad, qkv + [((1, s), i32)]),
        "segment flash fwd seq 4096 batch 2": (
            flash, [((2, s, h, d), bf)] * 3 + [((2, s), i32)]),
    }
    off_path.update(_kimi_linear_cases())
    off_path.update(_lfm2_moe_cases())
    off_path.update(_moonlight_cases())
    return dense, off_path


def _flash_grad(q, k, v):
    """Causal flash forward and backward by q, k and v, [B, S, H, D]."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd

    return jax.grad(lambda *a: flash_attention_bshd(
        *a, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _kimi_linear_cases():
    """The kernels of `train_kimilinear_seq8k` at its shapes: 2 rows x 8192,
    32 heads x 128 (KDA), query/key 192 beside value 128 (latent attention),
    8 held experts of [2304, 1024] over some 4096 routed rows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        _held_moe, held_rows)
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from paddle_tpu.ops.pallas.kda import kda_chunked

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    rows, s, h, hid, inter = 2, 8192, 32, 2304, 1024

    def kda_grad(q, k, v, g, beta):
        return jax.grad(lambda *a: kda_chunked(*a, interpret=False).astype(f32).sum(),
                        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    def gmm_grad(x, w, g):
        return jax.grad(lambda a, b: grouped_matmul(
            a, b, g, block_rows=128, backend="pallas", aligned=True).sum(),
            argnums=(0, 1))(x, w)

    laid_out, bm = held_rows(rows * s * 8, 8, 256)
    routing = (("kind", "sigmoid"), ("routed_scale", 2.446), ("renormalize", True))

    def experts_grad(x, logits, bias, *w):
        # the whole layer as the step runs it: route over 256, lay out the
        # pairs of the 8 held experts, the two row movers around three
        # grouped products, the shared expert
        return jax.grad(lambda a, *b: _held_moe(
            a, logits, bias, *b, k=8, first=0, routing=routing, rows=laid_out,
            block_rows=bm, backend="pallas", recompute=True)[0].astype(f32).sum(),
            argnums=tuple(range(7)))(x, *w)

    head = [((rows, s, h, 128), bf)] * 3
    return {
        "KDA scan fwd+bwd 2 x 8192 x 32 heads x 128": (
            kda_grad, head + [((rows, s, h, 128), f32), ((rows, s, h), f32)]),
        "flash fwd+bwd seq 8192, q/k 192, v 128": (
            _flash_grad, [((rows, s, h, 192), bf)] * 2 + [((rows, s, h, 128), bf)]),
        "grouped matmul fwd+dx+dw 5120 x 2304 -> 8 x [2304,1024]": (
            gmm_grad, [((5120, hid), bf), ((8, hid, inter), bf), ((5120,), i32)]),
        "grouped matmul fwd+dx+dw 5120 x 1024 -> 8 x [1024,2304]": (
            gmm_grad, [((5120, inter), bf), ((8, inter, hid), bf), ((5120,), i32)]),
        "held experts layer fwd+bwd 16384 tokens x 8 of 256": (
            experts_grad,
            [((rows * s, hid), bf), ((rows * s, 256), f32), ((256,), f32),
             ((8, hid, inter), bf), ((8, hid, inter), bf), ((8, inter, hid), bf),
             ((hid, inter), bf), ((hid, inter), bf), ((inter, hid), bf)]),
    }


def _lfm2_moe_cases():
    """The kernels of `train_lfm2moe_seq8k` at its shapes: 3 rows x 8192,
    32 query heads on 8 key heads of width 64 (half the lanes), and an expert
    layer WITHOUT a shared expert: 8 of 64 held, [2048, 1536], four a token."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        _held_moe, held_rows)

    bf, f32 = jnp.bfloat16, jnp.float32
    rows, s, hid, inter = 3, 8192, 2048, 1536

    laid_out, bm = held_rows(rows * s * 4, 8, 64)
    routing = (("kind", "sigmoid"), ("renormalize", True), ("renorm_eps", 1e-6))

    def experts_grad(x, logits, bias, *w):
        return jax.grad(lambda a, *b: _held_moe(
            a, logits, bias, *b, k=4, first=0, routing=routing, rows=laid_out,
            block_rows=bm, backend="pallas", recompute=True)[0].astype(f32).sum(),
            argnums=tuple(range(4)))(x, *w)

    return {
        "flash fwd+bwd 3 x 8192, 32q / 8kv x 64": (
            _flash_grad, [((rows, s, 32, 64), bf)] + [((rows, s, 8, 64), bf)] * 2),
        "held experts layer fwd+bwd 24576 tokens x 8 of 64, no shared expert": (
            experts_grad,
            [((rows * s, hid), bf), ((rows * s, 64), f32), ((64,), f32),
             ((8, hid, inter), bf), ((8, hid, inter), bf), ((8, inter, hid), bf)]),
    }


def _moonlight_cases():
    """The kernels of `train_moonlight_seq8k` at its shapes: 3 rows x 8192,
    16 heads of query/key 192 beside value 128, the grouped products at
    [2048, 1408] over the rows laid out for 18,432 pairs, and the whole expert
    layer at SIX experts a token (the two row movers at a `k` that is no power
    of two) with the two shared experts as one SwiGLU of 2816."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        _held_moe, held_rows)
    from paddle_tpu.ops.pallas.grouped_matmul import col_tiles, grouped_matmul
    from paddle_tpu.ops.pallas.moe_rows import rows_backend

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    rows, s, heads, hid, inter, k = 3, 8192, 16, 2048, 1408, 6

    laid_out, bm = held_rows(rows * s * k, 8, 64)
    buf = laid_out + 8 * bm
    # the column tiles of the forward, dx and dw as the kernels cut them
    tiles = lambda a, b: "/".join(map(str, col_tiles(bm, a, b, bf, bf).values()))  # noqa: E731
    # `xla` at these sizes: the movers' index arrays (3 x 73,728 pairs) pass
    # the scalar memory they may take, so the layout is XLA's gathers and
    # scatters and the layer compiles to 8 kernel calls, not 11 (PERF.md)
    movers = rows_backend("pallas", rows * s, hid, k, bf, buf, laid_out)

    def gmm_grad(x, w, g):
        return jax.grad(lambda a, b: grouped_matmul(
            a, b, g, block_rows=bm, backend="pallas", aligned=True).sum(),
            argnums=(0, 1))(x, w)

    routing = (("kind", "sigmoid"), ("routed_scale", 2.446), ("renormalize", True),
               ("renorm_eps", 1e-20))

    def experts_grad(x, logits, bias, *w):
        return jax.grad(lambda a, *b: _held_moe(
            a, logits, bias, *b, k=k, first=0, routing=routing, rows=laid_out,
            block_rows=bm, backend="pallas", recompute=True)[0].astype(f32).sum(),
            argnums=tuple(range(7)))(x, *w)

    return {
        "flash fwd+bwd 3 x 8192 x 16 heads, q/k 192, v 128": (
            _flash_grad, [((rows, s, heads, 192), bf)] * 2 + [((rows, s, heads, 128), bf)]),
        f"grouped matmul fwd+dx+dw {buf} x 2048 -> 8 x [2048,1408] (column tiles {tiles(hid, inter)})": (
            gmm_grad, [((buf, hid), bf), ((8, hid, inter), bf), ((buf,), i32)]),
        f"grouped matmul fwd+dx+dw {buf} x 1408 -> 8 x [1408,2048] (column tiles {tiles(inter, hid)})": (
            gmm_grad, [((buf, inter), bf), ((8, inter, hid), bf), ((buf,), i32)]),
        f"held experts layer fwd+bwd 24576 tokens x 6 a token, 8 of 64, two shared (row movers: {movers})": (
            experts_grad,
            [((rows * s, hid), bf), ((rows * s, 64), f32), ((64,), f32),
             ((8, hid, inter), bf), ((8, hid, inter), bf), ((8, inter, hid), bf),
             ((hid, 2 * inter), bf), ((hid, 2 * inter), bf), ((2 * inter, hid), bf)]),
    }


def _mesh_case(devices):
    """Attention + fused CE inside a dp=2 x mp=2 jit — the GSPMD program of
    the mesh train step, where a bare pallas_call is refused ("Mosaic
    kernels cannot be automatically partitioned") and must run per shard."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy_loss

    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "mp"))
    bf = jnp.bfloat16

    def loss(q, w, lab):
        out = F.scaled_dot_product_attention(
            Tensor(q), Tensor(q), Tensor(q), is_causal=True)._value
        x = out.reshape(-1, out.shape[2] * out.shape[3])
        return fused_linear_cross_entropy_loss(x, w, lab).mean()

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    args = [arg((2, 4096, 32, 128), bf, "dp", None, "mp", None),
            arg((4096, 32000), bf, None, "mp"), arg((2 * 4096,), jnp.int32,
                                                    "dp")]
    return jax.grad(loss, argnums=(0, 1)), args, mesh


def _compile(fn, specs, device):
    import jax
    from jax.sharding import SingleDeviceSharding

    args = [s if isinstance(s, jax.ShapeDtypeStruct) else
            jax.ShapeDtypeStruct(s[0], s[1],
                                 sharding=SingleDeviceSharding(device))
            for s in specs]
    t0 = time.perf_counter()
    try:
        lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
        kernels = lowered.as_text().count("tpu_custom_call")
        lowered.compile()
    except Exception as e:  # the compiler's refusal IS the result
        return {"ok": False, "error": f"{type(e).__name__}: {e}"[:600]}
    return {"ok": True, "tpu_custom_calls": kernels,
            "compile_s": round(time.perf_counter() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--extra", action="store_true",
                    help="also compile the kernels off the dense path "
                         "(record only)")
    ap.add_argument("--out", help="also write the JSON result to this file")
    args = ap.parse_args(argv)

    try:
        devices, stand_in = _target()
    except Exception as e:
        print(f"mosaic_aot_check: no TPU compiler reachable ({e})",
              file=sys.stderr)
        return 3
    import paddle_tpu  # noqa: F401  (x64 on, as every entry point runs)
    from paddle_tpu.ops.pallas import _compat

    if stand_in:
        # route the kernels as a TPU backend would; nothing here executes
        _compat.on_tpu = lambda: True
    device = devices[0]
    print(f"compiling for {device.device_kind} x{len(devices)} "
          f"({'compile-only stand-in' if stand_in else 'the local chips'})",
          flush=True)
    dense, off_path = _cases(args.extra)
    result = {"device_kind": device.device_kind, "compile_only": stand_in,
              "dense_path": {}, "off_path": {}}

    def run(group, name, fn, specs):
        res = _compile(fn, specs, device)
        result[group][name] = res
        print(f"  [{'ok' if res['ok'] else 'REFUSED'}] {name}"
              + ("" if res["ok"] else f": {res['error'][:300]}"), flush=True)

    for group, cases in (("dense_path", dense), ("off_path", off_path)):
        for name, (fn, specs) in cases.items():
            run(group, name, fn, specs)
    if len(devices) >= 4:
        from paddle_tpu.distributed.mesh import set_mesh

        fn, specs, mesh = _mesh_case(devices)
        set_mesh(mesh)   # read at trace time by the kernel entries
        try:
            run("dense_path",
                "flash + fused-CE fwd+bwd under a dp=2 x mp=2 jit", fn, specs)
        finally:
            set_mesh(None)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if all(r["ok"] for r in result["dense_path"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
