"""Tensor-parallel communication primitives.

Reference parity: fleet/layers/mpu/mp_ops.py — the identity/allreduce autograd
pairs (`_c_identity` forward=identity backward=allreduce, `_mp_allreduce`
forward=allreduce backward=identity), concat/split along mp group.

TPU-native: inside a compiled sharded program these are `lax.psum` /
`all_gather` over the "mp" mesh axis with jax's own transpose rules giving the
same fwd/bwd pairing; eagerly (global view) they are identities. Implemented
with custom_vjp so the pairing is explicit and matches Megatron semantics
exactly rather than relying on transposition.

The sequence split of the residual stream (Megatron sequence parallelism,
reference fleet/utils/sequence_parallel_utils.py) lives here too, ONCE for
both ways a program meets the "mp" axis: `seq_gather` (all-gather fwd,
reduce-scatter bwd), `seq_reduce_scatter` (reduce-scatter fwd, all-gather
bwd) and `seq_scatter` (slice fwd, all-gather bwd). Inside shard_map they are
the lax collectives; in a GSPMD program over a mesh whose "mp" axis has more
than one device they are layout constraints (the sequence dim split over "mp"
or whole, the batch over the data axes), from which the partitioner makes the
same collectives; with no such mesh they are identities.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.distributed.collective import _bound_axes

__all__ = ["_c_identity", "_mp_allreduce", "_c_concat", "_c_split",
           "mp_axis_bound", "MP_AXIS", "seq_gather", "seq_reduce_scatter",
           "seq_scatter", "sp_mesh"]

MP_AXIS = "mp"


def mp_axis_bound() -> bool:
    return bool(_bound_axes((MP_AXIS,)))


# -- identity fwd / psum bwd (column-parallel input) ------------------------
@jax.custom_vjp
def _identity_fwd_psum_bwd(x):
    return x


def _ifpb_fwd(x):
    return x, None


def _ifpb_bwd(_, g):
    if _bound_axes((MP_AXIS,)):
        g = jax.lax.psum(g, MP_AXIS)
    return (g,)


_identity_fwd_psum_bwd.defvjp(_ifpb_fwd, _ifpb_bwd)


# -- psum fwd / identity bwd (row-parallel output) --------------------------
@jax.custom_vjp
def _psum_fwd_identity_bwd(x):
    if _bound_axes((MP_AXIS,)):
        return jax.lax.psum(x, MP_AXIS)
    return x


def _pfib_fwd(x):
    return _psum_fwd_identity_bwd(x), None


def _pfib_bwd(_, g):
    return (g,)


_psum_fwd_identity_bwd.defvjp(_pfib_fwd, _pfib_bwd)


def _c_identity(tensor, group=None, skip_c_identity_dynamic=False):
    return apply_op(_identity_fwd_psum_bwd, tensor, name="c_identity")


def _mp_allreduce(tensor, group=None, use_calc_stream=True, use_model_parallel=True):
    return apply_op(_psum_fwd_identity_bwd, tensor, name="mp_allreduce")


def _c_concat(tensor, group=None):
    """all-gather along last dim over mp axis (fwd); slice (bwd)."""

    def f(v):
        if _bound_axes((MP_AXIS,)):
            return jax.lax.all_gather(v, MP_AXIS, axis=v.ndim - 1, tiled=True)
        return v

    return apply_op(f, tensor, name="c_concat")


def _c_split(tensor, group=None):
    """split last dim, keep local shard (fwd); all-gather (bwd)."""

    def f(v):
        if _bound_axes((MP_AXIS,)):
            n = jax.lax.axis_size(MP_AXIS)
            i = jax.lax.axis_index(MP_AXIS)
            sz = v.shape[-1] // n
            return jax.lax.dynamic_slice_in_dim(v, i * sz, sz, axis=v.ndim - 1)
        return v

    return apply_op(f, tensor, name="c_split")


# -- the sequence split (sequence parallelism over "mp") --------------------
def sp_mesh(x):
    """The global mesh when `x` is traced into a GSPMD program over a mesh
    whose "mp" axis has 2+ devices (the sequence-parallel stream), else None:
    outside a trace, inside shard_map, or without such a mesh."""
    from paddle_tpu.distributed.mesh import get_mesh

    mesh = get_mesh()
    if (mesh is None or int(mesh.shape.get(MP_AXIS, 1)) <= 1
            or not isinstance(x, jax.core.Tracer)
            or _bound_axes(tuple(mesh.axis_names))):
        return None
    return mesh


def _seq_layout(x, axis: int, batch: int | None, split: bool):
    """GSPMD: `x` with its sequence dim `axis` split over "mp" (or whole) and
    its batch dim `batch` over the data axes, where the sizes divide
    (`sp_mesh`). `batch == axis`: tokens flattened batch-major, the data axes
    first on that dim, then "mp"; `batch` None: no dim holds the batch."""
    from jax.sharding import NamedSharding, PartitionSpec

    from paddle_tpu.ops.pallas._compat import DATA_AXES, mesh_axes_dividing

    mesh = sp_mesh(x)
    if mesh is None:
        return x
    seq = (MP_AXIS,) if split else ()
    spec = [None] * x.ndim
    if batch == axis:
        spec[axis] = mesh_axes_dividing(mesh, DATA_AXES + seq, x.shape[axis])
    else:
        if batch is not None:
            spec[batch] = mesh_axes_dividing(mesh, DATA_AXES, x.shape[batch])
        spec[axis] = mesh_axes_dividing(mesh, seq, x.shape[axis])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def _gather(x, axis, batch):
    if _bound_axes((MP_AXIS,)):
        return jax.lax.all_gather(x, MP_AXIS, axis=axis, tiled=True)
    return _seq_layout(x, axis, batch, split=False)


def _sum_scatter(x, axis, batch):
    if _bound_axes((MP_AXIS,)):
        return jax.lax.psum_scatter(x, MP_AXIS, scatter_dimension=axis,
                                    tiled=True)
    return _seq_layout(x, axis, batch, split=True)


def _slice(x, axis, batch):
    if _bound_axes((MP_AXIS,)):
        n = jax.lax.axis_size(MP_AXIS)
        sz = x.shape[axis] // n
        return jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(MP_AXIS) * sz, sz, axis=axis)
    return _seq_layout(x, axis, batch, split=True)


def _pair(fwd, bwd):
    """custom_vjp `fwd` whose cotangent goes through `bwd` (dims static)."""
    f = jax.custom_vjp(fwd, nondiff_argnums=(1, 2))
    f.defvjp(lambda x, axis, batch: (fwd(x, axis, batch), None),
             lambda axis, batch, _, g: (bwd(g, axis, batch),))
    return f


_seq_gather = _pair(_gather, _sum_scatter)
_seq_reduce_scatter = _pair(_sum_scatter, _gather)
_seq_scatter = _pair(_slice, _gather)


def seq_gather(tensor, axis, batch):
    """The whole sequence (dim `axis`) on every "mp" rank (all-gather fwd,
    reduce-scatter of the partial cotangents bwd): a column-parallel
    projection's input. `batch`: the dim holding the batch (`_seq_layout`)."""
    return apply_op(lambda v: _seq_gather(v, axis, batch), tensor,
                    name="sp_allgather")


def seq_reduce_scatter(tensor, axis, batch):
    """Partial sums over "mp" summed onto this rank's sequence shard
    (all-gather bwd): a row-parallel projection's output."""
    return apply_op(lambda v: _seq_reduce_scatter(v, axis, batch), tensor,
                    name="sp_reduce_scatter")


def seq_scatter(tensor, axis, batch):
    """This rank's sequence shard of a tensor every rank holds whole
    (all-gather bwd)."""
    return apply_op(lambda v: _seq_scatter(v, axis, batch), tensor,
                    name="sp_scatter")
