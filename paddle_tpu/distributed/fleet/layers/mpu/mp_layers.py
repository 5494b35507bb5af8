"""Megatron-style tensor-parallel layers.

Reference parity: fleet/layers/mpu/mp_layers.py — `VocabParallelEmbedding`
(:47), `ColumnParallelLinear` (:334), `RowParallelLinear` (:541),
`ParallelCrossEntropy` (:742).

TPU-native: parameters carry logical FULL shapes annotated with an "mp"-axis
sharding (NamedSharding); the compiled program partitions them via GSPMD.
Under GSPMD with 2+ devices on "mp" the activations between the layers are
sequence-parallel (reference fleet/utils/sequence_parallel_utils.py): a
column-parallel layer all-gathers its input's sequence, a row-parallel one and
the embedding reduce-scatter their partial sums onto the sequence shards
(`mp_ops.seq_gather` / `seq_reduce_scatter`, custom-vjp pairs) — the bytes of
one all-reduce in two halves, each beside its product, and the norms and
residual adds between them on this rank's part of the sequence. Inside
shard_map (bound "mp") the layers keep Megatron's identity/psum pairs.
Eagerly on one chip they behave as their dense equivalents — same numerics,
so single-chip tests validate TP models.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import (
    MP_AXIS, _c_identity, _c_split, _mp_allreduce, _seq_reduce_scatter,
    mp_axis_bound, seq_gather, seq_reduce_scatter, sp_mesh,
)
from paddle_tpu.distributed.mesh import mesh_axis_size
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer.layers import Layer

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
           "ParallelCrossEntropy"]


def _annotate(p: Tensor, *spec):
    """Attach the logical mp sharding to a parameter (consumed by the train-step
    compiler in paddle_tpu.parallel when building NamedShardings)."""
    p._mp_pspec = spec
    return p


class VocabParallelEmbedding(Layer):
    """reference: mp_layers.py:47 — vocab dim sharded over mp ranks; out-of-shard
    ids produce zeros locally, summed back by allreduce."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.world_size = mesh_axis_size(MP_AXIS)
        self.weight = _annotate(
            self.create_parameter([num_embeddings, embedding_dim], weight_attr,
                                  default_initializer=I.XavierNormal()),
            MP_AXIS, None,
        )

    def forward(self, x):
        bound = mp_axis_bound()
        mesh = None if bound else sp_mesh(x._value if isinstance(x, Tensor) else x)
        mp = int(mesh.shape[MP_AXIS]) if mesh is not None else 1
        if not bound and (mesh is None or x.ndim != 2
                          or self.num_embeddings % mp or x.shape[1] % mp):
            # eager / one device / shapes mp does not divide: logical full
            # weight, partitioning (if any) via _annotate
            return F.embedding(x, self.weight)

        # this rank's vocab shard: shift ids into the local range, zero the
        # out-of-shard rows (reference mp_layers.py:47 masks against
        # [vocab_start, vocab_end)); the partial sums are then added over mp
        def f(ids, w):
            n_local = w.shape[0]
            start = jax.lax.axis_index(MP_AXIS) * n_local
            local = ids - start
            in_range = (local >= 0) & (local < n_local)
            safe = jnp.clip(local, 0, n_local - 1)
            out = jnp.take(w, safe, axis=0)
            return jnp.where(in_range[..., None], out, jnp.zeros((), out.dtype))

        if bound:       # manual (shard_map) path: the local weight is given
            out = apply_op(f, x, self.weight, name="vocab_parallel_embedding")
            return _mp_allreduce(out)
        # GSPMD over mp: the same per shard, summed onto the sequence shards
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.distributed.mesh import shard_map_compat
        from paddle_tpu.ops.pallas._compat import DATA_AXES, mesh_axes_dividing

        data = mesh_axes_dividing(mesh, DATA_AXES, x.shape[0])
        per_shard = shard_map_compat(
            lambda ids, w: _seq_reduce_scatter(f(ids, w), 1, 0), mesh,
            (P(data, None), P(MP_AXIS, None)), P(data, MP_AXIS, None))
        return apply_op(per_shard, x, self.weight,
                        name="vocab_parallel_embedding")


class ColumnParallelLinear(Layer):
    """reference: mp_layers.py:334 — weight [in, out] sharded on out dim."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.world_size = mesh_axis_size(MP_AXIS)
        self.weight = _annotate(
            self.create_parameter([in_features, out_features], weight_attr,
                                  default_initializer=I.XavierNormal()),
            None, MP_AXIS,
        )
        self.bias = (
            _annotate(self.create_parameter([out_features], None, is_bias=True), MP_AXIS)
            if has_bias else None
        )

    def forward(self, x):
        # input whole across mp: identity fwd / psum bwd on the input edge
        # (shard_map), or the sequence gathered here (GSPMD)
        x = _c_identity(x) if mp_axis_bound() else seq_gather(x, x.ndim - 2, 0)
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output and mp_axis_bound():
            from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import _c_concat

            out = _c_concat(out)
        return out


class RowParallelLinear(Layer):
    """reference: mp_layers.py:541 — weight [in, out] sharded on in dim;
    partial outputs summed by allreduce (identity bwd)."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.world_size = mesh_axis_size(MP_AXIS)
        self.weight = _annotate(
            self.create_parameter([in_features, out_features], weight_attr,
                                  default_initializer=I.XavierNormal()),
            MP_AXIS, None,
        )
        self.bias = self.create_parameter([out_features], None, is_bias=True) if has_bias else None

    def forward(self, x):
        if not self.input_is_parallel:
            x = _c_split(x)
        out = F.linear(x, self.weight, None)
        if mp_axis_bound():
            out = _mp_allreduce(out)
        else:
            out = seq_reduce_scatter(out, out.ndim - 2, 0)
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    """reference: mp_layers.py:742 — softmax CE over vocab sharded on mp.

    TPU-native: logits stay vocab-sharded; the max/denominator reduce with
    psum over the mp axis so no rank materializes the full vocab row. The
    hot path is the chunked fused CE kernel — `F.parallel_cross_entropy`
    (`paddle_tpu.ops.pallas.fused_ce`), escape hatch
    `use_fused_cross_entropy=False`.
    """

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return F.parallel_cross_entropy(input, label,
                                        ignore_index=self.ignore_index)
