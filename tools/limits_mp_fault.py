#!/usr/bin/env python3
"""A mesh cell's limits read against a fault of the program: benchmark/limits.py
with ONE sum over "mp" left out.

    python3 tools/limits_mp_fault.py --workload train_mistral7b_seq4k_dp2mp2 \\
        --seeds <n> --controls 1 --extra control --seconds 1

The first decoder layer's o_proj keeps this chip's partial product on its
sequence shard where the program reduce-scatters both chips' partials (the
sequence-parallel stream, `mpu/mp_ops.py`). The line's `program` numbers are
then the fault's gaps against the float32 reference; `control_fp8` is the
reference in float8, as benchmark/limits.py reads it. One process, on the
cell's chips; `--rehearsal` runs it on virtual CPU devices at tiny sizes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from paddle_tpu.core.tensor import Tensor, apply_op  # noqa: E402
from paddle_tpu.distributed.fleet.layers.mpu import mp_layers  # noqa: E402
from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import MP_AXIS, sp_mesh  # noqa: E402
from paddle_tpu.distributed.mesh import shard_map_compat  # noqa: E402
from paddle_tpu.ops.pallas._compat import DATA_AXES, mesh_axes_dividing  # noqa: E402

_row_forward = mp_layers.RowParallelLinear.forward
_faulted = []


def _without_sum(self, x):
    mesh = sp_mesh(x._value if isinstance(x, Tensor) else x)
    if mesh is None or (_faulted and _faulted[0] is not self):
        return _row_forward(self, x)
    _faulted[:] = [self]        # the first row-parallel layer the step traces

    def partial(xs, w):
        y = xs @ w
        n = y.shape[1] // jax.lax.axis_size(MP_AXIS)
        return jax.lax.dynamic_slice_in_dim(y, jax.lax.axis_index(MP_AXIS) * n, n, axis=1)

    data = mesh_axes_dividing(mesh, DATA_AXES, x.shape[0])
    f = shard_map_compat(partial, mesh, (P(data, None, MP_AXIS), P(MP_AXIS, None)),
                         P(data, MP_AXIS, None))
    return apply_op(f, x, self.weight, name="row_parallel_without_sum")


if __name__ == "__main__":
    from benchmark import limits

    mp_layers.RowParallelLinear.forward = _without_sum
    code = limits.main()
    if not _faulted:
        raise SystemExit("no sum over mp was left out: the cell has no mesh with mp > 1")
    sys.exit(code)
