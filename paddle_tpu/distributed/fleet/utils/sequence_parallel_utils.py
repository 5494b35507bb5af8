"""Megatron-style sequence parallelism inside the TP group.

Reference parity: fleet/utils/sequence_parallel_utils.py — `ScatterOp` (:85),
`AllGatherOp` (:111), `ReduceScatterOp` (:127), `ColumnSequenceParallelLinear`
(:427), `RowSequenceParallelLinear`, `register_sequence_parallel_allreduce_hooks`
(:192), `mark_as_sequence_parallel_parameter`.

TPU-native: the sequence dim is sharded over the "mp" axis between attention
blocks; scatter/all-gather are the ONE sequence split of `mpu/mp_ops.py`
(custom-vjp pairs, all_gather fwd <-> reduce_scatter bwd): lax collectives
inside shard_map, layout constraints in a GSPMD program, where the mpu layers
use them whenever the mesh's "mp" axis has more than one device. These
layers keep the reference's [seq, batch, hidden] layout: sequence dim 0.
"""
from __future__ import annotations

import jax

from paddle_tpu.core.tensor import apply_op
from paddle_tpu.distributed.collective import _bound_axes
from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import (
    MP_AXIS, seq_gather, seq_reduce_scatter, seq_scatter,
)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer.layers import Layer

__all__ = ["ScatterOp", "AllGatherOp", "ReduceScatterOp", "scatter", "all_gather",
           "reduce_scatter", "identity_in_fwd_allreduce_in_bwd",
           "ColumnSequenceParallelLinear", "RowSequenceParallelLinear",
           "mark_as_sequence_parallel_parameter",
           "register_sequence_parallel_allreduce_hooks"]


def _batch_dim(x):
    """[seq, batch, ...]: the batch is dim 1; [seq * batch, hidden] (the
    sequence major) has no dim of its own for it."""
    return 1 if x.ndim >= 3 else None


def scatter(x):
    """This rank's part of the sequence (dim 0) of `x` (all-gather bwd)."""
    return seq_scatter(x, 0, _batch_dim(x))


def all_gather(x):
    """The whole sequence (dim 0) from every rank's part (reduce-scatter bwd)."""
    return seq_gather(x, 0, _batch_dim(x))


def reduce_scatter(x):
    """Partial sums over "mp" summed onto this rank's part of the sequence
    (all-gather bwd)."""
    return seq_reduce_scatter(x, 0, _batch_dim(x))


# PyLayer-style aliases matching the reference class names
class ScatterOp:
    apply = staticmethod(scatter)


class AllGatherOp:
    apply = staticmethod(all_gather)


class ReduceScatterOp:
    apply = staticmethod(reduce_scatter)


def identity_in_fwd_allreduce_in_bwd(x):
    from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import _c_identity

    return _c_identity(x)


def mark_as_sequence_parallel_parameter(parameter):
    parameter.sequence_parallel = True


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_sequence_parallel_allreduce=False):
    """reference :192 — allreduce grads of sequence-parallel params (LayerNorm
    etc.) over the mp group after backward. Implemented as tensor grad hooks."""

    def make_hook():
        def hook(grad):
            axes = _bound_axes((MP_AXIS,))
            if axes:
                return apply_op(lambda v: jax.lax.psum(v, axes), grad, name="sp_allreduce")
            return grad

        return hook

    for p in model.parameters():
        if getattr(p, "sequence_parallel", False):
            p.register_hook(make_hook())


class ColumnSequenceParallelLinear(Layer):
    """reference :427 — allgather(seq) -> column linear."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=False, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter([in_features, out_features], weight_attr,
                                            default_initializer=I.XavierNormal())
        self.weight._mp_pspec = (None, MP_AXIS)
        self.bias = self.create_parameter([out_features], None, is_bias=True) if has_bias else None

    def forward(self, x):
        x = all_gather(x)
        return F.linear(x, self.weight, self.bias)


class RowSequenceParallelLinear(Layer):
    """row linear -> reduce_scatter(seq)."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter([in_features, out_features], weight_attr,
                                            default_initializer=I.XavierNormal())
        self.weight._mp_pspec = (MP_AXIS, None)
        self.bias = self.create_parameter([out_features], None, is_bias=True) if has_bias else None

    def forward(self, x):
        out = F.linear(x, self.weight, None)
        out = reduce_scatter(out)
        if self.bias is not None:
            out = out + self.bias
        return out
