"""Shared Pallas-kernel helpers: the one platform probe, the x64 trace
override, the VMEM a kernel may ask for, and the shard_map wrap Mosaic
kernels need under a GSPMD mesh."""
from __future__ import annotations

import contextlib

import jax

__all__ = ["on_tpu", "lanes", "vmem_budget", "x64_off", "kernel_trace_ctx", "kernel_name",
           "DATA_AXES", "mesh_axes_dividing", "gspmd_mesh"]

# the mesh axes a batch dim is sharded over (io.device_feed.default_batch_spec)
DATA_AXES = ("dp", "sharding")


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU — the ONE probe every kernel
    module routes on (Mosaic on TPU, interpret mode elsewhere). A backend
    that fails to start raises here: turning that into "not a TPU" would
    silently select interpret mode on a machine that has a chip."""
    return jax.devices()[0].platform == "tpu"


def lanes(n: int) -> int:
    """n rounded up to whole 128-lane rows: what a trailing dim of n takes
    in VMEM."""
    return -(-n // 128) * 128


_V5E_VMEM = 128 * 2**20     # one v5e TensorCore's, the chip every cell runs on


def vmem_budget() -> int:
    """What a kernel may ask Mosaic for (`vmem_limit_bytes`) beside the
    compiler's own 16 MiB scope: half of one TensorCore's VMEM as
    `pltpu.get_tpu_info` gives it for the chip, 64 MiB on a v5e. Where the
    process holds no TPU (interpret mode, or `tools/aot_step.py` lowering for
    a v5e with `on_tpu` forced) it is a v5e's half: this reads the device,
    not the path `on_tpu` picks."""
    if jax.devices()[0].platform != "tpu":
        return _V5E_VMEM // 2
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.get_tpu_info().vmem_capacity_bytes // 2


def x64_off():
    """x64 mode (paddle int64 parity, enabled at package import) makes Pallas
    index maps emit i64 constants Mosaic can't legalize; trace the
    pallas_call with it off."""
    return jax.enable_x64(False)


def kernel_trace_ctx(interpret: bool):
    """Context for tracing a pallas_call: `x64_off()` on the Mosaic path,
    a no-op in interpret mode.

    Interpret mode must trace under the ambient x64 setting: when the call
    sits inside an outer `jax.jit`, its grid/loop machinery is lowered only
    when the OUTER program lowers — after this context has exited — and a
    jaxpr traced x32 but lowered x64 re-canonicalizes weak int literals into
    i64/i32 StableHLO verifier mismatches. Mosaic never defers past the
    context (and needs x64 off for its index types), so the TPU path keeps
    the override."""
    return contextlib.nullcontext() if interpret else x64_off()


def kernel_name(name: str) -> dict:
    """The `pallas_call` keywords that give a kernel its stable name: Mosaic
    writes them into the custom call as `kernel_name` and `kernel_metadata`,
    which is how a device trace tells one kernel from another (PERF.md
    section 3 says where each arrives on a v5e)."""
    return {"name": name, "metadata": {"kernel": name}}


def mesh_axes_dividing(mesh, names, *sizes):
    """The subset of mesh axes `names` (present, size > 1) whose combined
    size divides every one of `sizes`, as a PartitionSpec entry (None when
    nothing applies — that dim stays replicated)."""
    axes, div = [], 1
    for a in names:
        n = int(mesh.shape.get(a, 1))
        if n > 1 and all(s % (div * n) == 0 for s in sizes):
            axes.append(a)
            div *= n
    return tuple(axes) if axes else None


def gspmd_mesh(*args):
    """The global mesh when `args` are being traced into a multi-device
    GSPMD program, else None.

    Mosaic kernels cannot be auto-partitioned: a `pallas_call` lowered inside
    a `jit` that spans more than one device raises NotImplementedError
    ("wrap the call in a shard_map"). The mesh-compiled train step is such a
    jit, so a kernel entry that gets a mesh back here runs its pallas_call
    under `shard_map_compat` with the layout GSPMD already gives the
    operands (batch over DATA_AXES, heads / vocab over "mp"). Inside an
    enclosing shard_map (bound axes) or outside any trace the kernel is
    called as is."""
    from paddle_tpu.distributed.collective import _bound_axes
    from paddle_tpu.distributed.mesh import get_mesh

    mesh = get_mesh()
    if (mesh is None or mesh.size == 1
            or not any(isinstance(a, jax.core.Tracer) for a in args)
            or _bound_axes(tuple(mesh.axis_names))):
        return None
    return mesh
