"""Scan-over-layers compilation + policy-based selective rematerialization.

Reference analog: the recompute pass / `recompute_interval` knob of the
reference's hybrid-parallel stack (fleet recompute, SURVEY §2.1) — but
TPU-native, the T5X/MaxText way:

* **Scan-over-layers.** A homogeneous decoder stack (N identical layers) is
  executed as ONE `jax.lax.scan` over the layer parameters stacked along a
  leading layer axis, so the traced program contains the layer body once and
  HLO size / XLA compile time are O(1) in depth instead of O(N).
* **Selective remat policies.** The all-or-nothing `remat: bool` knob becomes
  a policy string applied PER LAYER via `jax.checkpoint` +
  `jax.checkpoint_policies`:

    - ``none``              no rematerialization (save everything XLA keeps)
    - ``full``              `jax.checkpoint` default: save only layer
                            boundaries, recompute the layer interior
    - ``save_nothing``      explicit `nothing_saveable` (alias of ``full``'s
                            default policy, spelled out)
    - ``save_dots``         `dots_with_no_batch_dims_saveable`: keep matmul
                            outputs, recompute the cheap elementwise tail
    - ``offload_residuals`` residual-stream activations (tagged
                            `checkpoint_name(..., "residual")` by the layer)
                            are offloaded to pinned host memory via
                            `save_and_offload_only_these_names` when the
                            backend has one (`host_memory_supported()`),
                            else saved on device (`save_only_these_names`)

  Because the policy wraps each layer (or the scan body), the embed / fused
  LM-head / CE segment is NEVER inside a remat region: the fused head is
  computed exactly once even under ``full``.

Cooperation protocol (how a compiled step talks to a model):

* A model that can apply per-layer remat itself sets
  ``layer_remat_capable = True`` and reads :func:`current_layer_ctx` in its
  forward. `CompiledTrainStep` then delivers the policy via
  :func:`layer_execution` instead of wrapping the whole loss in
  `jax.checkpoint` (the legacy behavior, kept for non-cooperating models).
* A model whose homogeneous stack can be scanned exposes ``scan_group()``
  returning the list of identical layers. `CompiledTrainStep(scan_layers=
  True)` stacks each layer parameter across the group OUTSIDE the program
  (one `[L, ...]` jit input per parameter) and delivers the stacked arrays
  through the same context; the model consumes them with
  :func:`scan_layer_stack`.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "REMAT_POLICIES", "normalize_remat", "remat_wrap", "layer_execution",
    "current_layer_ctx", "LayerExecContext", "stack_layer_vals",
    "scan_layer_stack", "unrolled_layer_call", "ScanShardInfo",
]

REMAT_POLICIES = ("none", "full", "save_dots", "save_nothing",
                  "offload_residuals")

# checkpoint_name tag the decoder layers put on their residual stream; the
# offload_residuals policy keys on it
RESIDUAL_TAG = "residual"


def normalize_remat(remat) -> str:
    """Map the legacy bool knob onto the policy namespace.

    True -> 'full' (the old whole-graph remat semantics, now applied per
    layer for cooperating models), False/None -> 'none'; policy strings pass
    through validated.
    """
    if remat is None or remat is False:
        return "none"
    if remat is True:
        return "full"
    policy = str(remat)
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}; expected one of "
            f"{'|'.join(REMAT_POLICIES)} (or a bool)")
    return policy


def _offload_policy():
    from paddle_tpu.parallel.train_step import host_memory_supported

    if host_memory_supported():
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=[RESIDUAL_TAG],
            offload_src="device", offload_dst="pinned_host")
    # no pinned-host space (CPU test backend): degrade to device-saved names,
    # preserving the recompute structure (and numerics) of the offload policy
    return jax.checkpoint_policies.save_only_these_names(RESIDUAL_TAG)


def remat_wrap(fn: Callable, policy: str, in_scan: bool = False) -> Callable:
    """Wrap `fn` (a pure jax function) in `jax.checkpoint` per `policy`.

    `in_scan=True` relaxes `prevent_cse` (safe and faster under
    `lax.scan`/`while`, per the jax.checkpoint docs).
    """
    policy = normalize_remat(policy)
    if policy == "none":
        return fn
    kw = dict(prevent_cse=not in_scan)
    if policy == "save_nothing":
        kw["policy"] = jax.checkpoint_policies.nothing_saveable
    elif policy == "save_dots":
        kw["policy"] = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif policy == "offload_residuals":
        kw["policy"] = _offload_policy()
    # 'full': jax.checkpoint's default (save only the wrapped fn's inputs)
    return jax.checkpoint(fn, **kw)


class ScanShardInfo:
    """ZeRO-3 layout contract for a scan-stacked layer group.

    cols: one ``(shard_spec, full_spec)`` PartitionSpec pair PER group column,
    for the PER-LAYER slice (the stacked array minus its leading layer dim).
    ``shard_spec`` is how the column persists between steps (reduce-scattered
    over the sharding axis); ``full_spec`` is its layout while a layer is
    being computed (mp-only sharding). mode: ``"ahead"`` = double-buffered
    gather of layer k+1 while layer k computes (at most 2 layers of full
    weights live); ``"start"`` = all-gather the whole stack up front (the
    overlap-free baseline).
    """

    __slots__ = ("mesh", "cols", "mode", "axis", "act_spec")

    def __init__(self, mesh, cols, mode: str = "ahead", axis: str = "sharding",
                 act_spec=None):
        if mode not in ("ahead", "start"):
            raise ValueError(
                f"unknown zero3 gather mode {mode!r}; expected 'ahead'|'start'")
        self.mesh = mesh
        self.cols = list(cols)
        self.mode = mode
        self.axis = axis
        # layout of the carried hidden state (the step's batch spec): pinning
        # the layer-boundary activations stops the partitioner from resharding
        # the saved boundaries onto the weight axes between fwd and bwd
        self.act_spec = act_spec


class LayerExecContext:
    """What a compiled step asks of a cooperating model's layer stack."""

    __slots__ = ("policy", "stacked", "shard_info")

    def __init__(self, policy: str = "none", stacked=None, shard_info=None):
        self.policy = policy
        # stacked: per-parameter [L, ...] arrays for the model's scan_group()
        # (stacked OUTSIDE the traced program), or None when the model should
        # use its own (bound) per-layer parameters
        self.stacked = stacked
        # shard_info: ScanShardInfo when the stacked arrays persist ZeRO-3
        # reduce-scattered and the scan loop must (un)gather them itself
        self.shard_info = shard_info


class _CtxTLS(threading.local):
    def __init__(self):
        self.ctx = None


_tls = _CtxTLS()


def current_layer_ctx() -> LayerExecContext | None:
    return _tls.ctx


@contextmanager
def layer_execution(policy: str = "none", stacked=None, shard_info=None):
    prev = _tls.ctx
    _tls.ctx = LayerExecContext(policy, stacked, shard_info)
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


def stack_layer_vals(per_layer_vals: Sequence[Sequence]) -> list:
    """list[L][P] parameter values -> list[P] arrays stacked on a new leading
    layer axis (the MaxText/T5X scanned-weights layout)."""
    n = len(per_layer_vals[0])
    for lp in per_layer_vals:
        if len(lp) != n:
            raise ValueError("scan group layers are not homogeneous")
    return [jnp.stack([lp[j] for lp in per_layer_vals]) for j in range(n)]


def _fold_rng(idx):
    """Scope fleet RNG streams by layer index: the scan body traces ONCE, so
    without the fold every layer would replay identical dropout keys."""
    from contextlib import contextmanager as _cm

    from paddle_tpu.distributed.fleet import rng as fleet_rng

    @_cm
    def scope():
        prev = fleet_rng._tls.active_key_fn
        if prev is not None:
            fleet_rng._tls.active_key_fn = \
                lambda: jax.random.fold_in(prev(), idx)
        try:
            yield
        finally:
            fleet_rng._tls.active_key_fn = prev

    return scope()


def scan_layer_stack(template, stacked_vals: Sequence, x, args: tuple = (),
                     kwargs: dict | None = None, policy: str = "none",
                     shard_info: ScanShardInfo | None = None):
    """Run a homogeneous layer stack as `jax.lax.scan` over stacked params.

    template: one layer instance (the body is traced through it via
    `functional_call`, so its parameter Tensors are only used as binding
    slots). stacked_vals: one [L, ...] array per template parameter. x: the
    carried hidden-state ARRAY. args/kwargs: broadcast (layer-invariant)
    extras passed to every layer call. Returns the final hidden array.

    shard_info (ZeRO-3): the stacked arrays persist reduce-scattered over the
    sharding axis. mode "ahead" runs the double-buffered gather-ahead scan
    (layer k+1's weights all-gather while layer k computes; backward
    re-gathers and emits reduce-scatter gradients — at most 2 layers of full
    weights are ever live). mode "start" all-gathers the whole stack before
    the loop (the overlap-free baseline).
    """
    from paddle_tpu.amp import fp8 as _fp8
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.parallel.train_step import functional_call

    kwargs = kwargs or {}
    if shard_info is not None:
        sess = _fp8.current_session()
        if sess is not None and sess.mode != "stateless":
            # the zero3 custom-vjp scan owns its residuals/cotangents and
            # cannot thread the delayed-scaling amax state; CompiledTrainStep
            # rejects the combination up front — this is the backstop
            raise ValueError(
                "fp8 delayed scaling cannot thread the zero_stage=3 "
                "sharded-weights scan; use zero_stage<=2 with fp8_policy")
        return _zero3_scan(template, stacked_vals, x, args, kwargs,
                           shard_info)
    n_layers = stacked_vals[0].shape[0]
    n_cols = len(stacked_vals)
    # delayed-scaling fp8: stacked [L, H] amax histories for the callsites
    # inside the layer body ride the scan xs; their per-layer cotangents
    # (the updated histories) re-stack through the scan's vjp
    fp8_leaves = _fp8.scan_enter(n_layers)

    def body(carry, xs):
        idx = xs[0]
        layer_vals = list(xs[1:1 + n_cols])
        with _fold_rng(idx), _fp8.scan_body(list(xs[1 + n_cols:])):
            out = functional_call(template, layer_vals, (Tensor(carry),) + args,
                                  kwargs=kwargs)
        return (out._value if isinstance(out, Tensor) else out), None

    body = remat_wrap(body, policy, in_scan=True)
    xs = (jnp.arange(n_layers),) + tuple(stacked_vals) + tuple(fp8_leaves)
    h, _ = jax.lax.scan(body, x, xs)
    _fp8.scan_exit()
    return h


def _rng_base_raw():
    """Snapshot the active fleet RNG stream as raw key data (or None).

    The zero3 custom-vjp scan re-traces the layer body when the backward
    re-gathers weights; a thread-local key FUNCTION would be gone (or its
    fold counter advanced) by then, so the per-stack base key is captured
    once as a VALUE and threaded through the vjp explicitly."""
    from paddle_tpu.distributed.fleet import rng as fleet_rng

    fn = fleet_rng._tls.active_key_fn
    if fn is None:
        return None
    return jax.random.key_data(fn())


@contextmanager
def _rng_from_raw(key_raw, idx):
    """Install a per-layer fleet RNG stream derived from captured raw key
    data (the replayable counterpart of `_fold_rng`)."""
    from paddle_tpu.distributed.fleet import rng as fleet_rng

    prev = fleet_rng._tls.active_key_fn
    if key_raw is not None:
        base = jax.random.wrap_key_data(key_raw)
        fleet_rng._tls.active_key_fn = lambda: jax.random.fold_in(base, idx)
    try:
        yield
    finally:
        fleet_rng._tls.active_key_fn = prev


def _zero_cotangent(v):
    """A zero cotangent of the right kind: float0 for integer/key primals."""
    if jnp.issubdtype(v.dtype, jnp.floating) or jnp.issubdtype(
            v.dtype, jnp.complexfloating):
        return jnp.zeros(v.shape, v.dtype)
    return np.zeros(v.shape, jax.dtypes.float0)


def _zero3_scan(template, stacked_vals, x, args, kwargs,
                shard_info: ScanShardInfo):
    """The ZeRO-3 scan loop: double-buffered gather-ahead forward, re-gather
    + reduce-scatter backward, as one `jax.custom_vjp`.

    Why a custom vjp instead of `jax.checkpoint`: the prefetched full weights
    ride the scan CARRY, and anything in the carry is a saved residual under
    every checkpoint policy — plain AD (or remat) would therefore keep ALL L
    layers of gathered weights live for the backward, defeating the sharding.
    Owning the vjp pins the residuals to exactly (layer-boundary activations,
    the reduce-scattered stacks): forward gathers layer k+1 while layer k
    computes; backward runs the mirror-image scan (gather layer k-1 while
    layer k's grads compute), recomputing each layer interior — the
    PyTorch-FSDP/ZeRO-3 schedule, so the layer interior is implicitly
    remat'd 'full' regardless of the session policy.

    Gradients w.r.t. the stacked params leave each backward iteration through
    a `with_sharding_constraint` to the reduce-scattered layout: with the
    batch sharded over the same axis the partial-sum dW lowers to a
    reduce-scatter instead of an all-reduce, and the optimizer update runs
    on the shard.

    mode "start" (the baseline) shares this exact vjp structure —
    identical residuals, identical per-layer dW scatter — but gathers the
    WHOLE stack before each loop instead of one layer ahead, so the
    measured difference between the modes is purely the gather schedule."""
    from jax.sharding import NamedSharding, PartitionSpec

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.parallel.train_step import functional_call

    mesh = shard_info.mesh
    zaxis = shard_info.axis
    zsize = int(mesh.shape[zaxis])
    full_sh = [NamedSharding(mesh, PartitionSpec(*tuple(f)))
               for _, f in shard_info.cols]
    shard_sh = [NamedSharding(mesh, PartitionSpec(*tuple(s)))
                for s, _ in shard_info.cols]
    n_layers = int(stacked_vals[0].shape[0])
    n_cols = len(stacked_vals)

    # -- flat-buffer packing (the FSDP flat-parameter trick) ----------------
    # A layer's columns whose ONLY sharded dim is the zero axis are packed
    # into one [Z, T] buffer, so the layer costs ONE all-gather (and its
    # grads ONE reduce-scatter) instead of one per column — collective
    # launch/rendezvous overhead is what eats the overlap win otherwise.
    # Columns that also carry mp sharding keep the per-column path (packing
    # would flatten the mp dim into the buffer and un-shard it).
    slice_shapes = [tuple(v.shape[1:]) for v in stacked_vals]
    packed_cols = []  # (col_index, sharded_dim, flat_size_per_group)
    loose_cols = []
    for i, (s, f) in enumerate(shard_info.cols):
        sdims = tuple(s)
        d = next((j for j, e in enumerate(sdims) if e == zaxis), None)
        only_zero = all(e is None for j, e in enumerate(sdims) if j != d) \
            and all(e is None for e in tuple(f))
        if d is not None and only_zero and slice_shapes[i]:
            packed_cols.append((i, d))
        else:
            loose_cols.append(i)

    def _pack(vals):
        """Per-layer column slices -> ONE [Z, T] buffer (pure local reshapes:
        the sharded dim moves to the front and splits into Z groups)."""
        groups = []
        for i, d in packed_cols:
            v = jnp.moveaxis(vals[i], d, 0)
            groups.append(v.reshape((zsize, -1)))
        return jnp.concatenate(groups, axis=1)

    def _unpack(packed):
        """[Z, T] buffer -> per-layer column slices (inverse of `_pack`)."""
        out = {}
        off = 0
        for i, d in packed_cols:
            shape = slice_shapes[i]
            moved = (shape[d],) + shape[:d] + shape[d + 1:]
            sz = int(np.prod(moved)) // zsize
            piece = packed[:, off:off + sz]
            off += sz
            v = piece.reshape((zsize, moved[0] // zsize) + moved[1:])
            v = v.reshape(moved)
            out[i] = jnp.moveaxis(v, 0, d)
        return out

    pack_full_sh = NamedSharding(mesh, PartitionSpec())
    pack_shard_sh = NamedSharding(mesh, PartitionSpec(zaxis))

    def gather(vals):
        """Reconstitute one layer's full weights: one packed all-gather +
        per-column gathers for the mp-sharded leftovers."""
        out = list(vals)
        if packed_cols:
            packed = jax.lax.with_sharding_constraint(_pack(vals),
                                                      pack_shard_sh)
            full = jax.lax.with_sharding_constraint(packed, pack_full_sh)
            for i, v in _unpack(full).items():
                out[i] = v
        for i in loose_cols:
            out[i] = jax.lax.with_sharding_constraint(vals[i], full_sh[i])
        return out

    def scatter(grads):
        """One layer's full dW -> the reduce-scattered layout: one packed
        reduce-scatter + per-column constraints for the leftovers."""
        out = list(grads)
        if packed_cols:
            packed = jax.lax.with_sharding_constraint(_pack(grads),
                                                      pack_shard_sh)
            for i, v in _unpack(packed).items():
                out[i] = jax.lax.with_sharding_constraint(v, shard_sh[i])
        for i in loose_cols:
            out[i] = jax.lax.with_sharding_constraint(grads[i], shard_sh[i])
        return out

    act_sh = (NamedSharding(mesh, PartitionSpec(*tuple(shard_info.act_spec)))
              if shard_info.act_spec is not None else None)

    def pin_act(h):
        return (jax.lax.with_sharding_constraint(h, act_sh)
                if act_sh is not None else h)

    # broadcast extras (attn_mask / rope / segment metadata) must be explicit
    # vjp primals: custom_vjp functions may not close over outer-jit tracers
    extra_leaves, extra_tree = jax.tree_util.tree_flatten(
        (tuple(args), dict(kwargs)),
        is_leaf=lambda v: isinstance(v, Tensor))
    extra_arrs, extra_slots, extra_static = [], [], []
    for leaf in extra_leaves:
        v = leaf._value if isinstance(leaf, Tensor) else leaf
        if isinstance(v, (jax.Array, np.ndarray)) or hasattr(v, "dtype"):
            extra_slots.append(len(extra_arrs))
            extra_arrs.append(jnp.asarray(v))
            extra_static.append(None)
        else:
            extra_slots.append(None)
            extra_static.append(leaf)

    def rebuild_extras(arrs):
        leaves = [extra_static[i] if s is None else arrs[s]
                  for i, s in enumerate(extra_slots)]
        return jax.tree_util.tree_unflatten(extra_tree, leaves)

    key_raw = _rng_base_raw()
    has_rng = key_raw is not None
    if key_raw is None:
        key_raw = jnp.zeros((2,), jnp.uint32)  # placeholder primal slot

    def apply_layer(idx, w_full, h, kraw, extras):
        a, kw = rebuild_extras(extras)
        with _rng_from_raw(kraw if has_rng else None, idx):
            out = functional_call(template, list(w_full),
                                  (Tensor(h),) + tuple(a), kwargs=kw)
        return out._value if isinstance(out, Tensor) else out

    ahead = shard_info.mode == "ahead"
    stacked_full_sh = [
        NamedSharding(mesh, PartitionSpec(None, *tuple(f)))
        for _, f in shard_info.cols]

    def gather_stack(stacked):
        """mode 'start': unshard every layer's weights up front."""
        return [jax.lax.with_sharding_constraint(v, sh)
                for v, sh in zip(stacked, stacked_full_sh)]

    def _fwd_scan(h0, kraw, stacked, extras):
        if not ahead:
            full = gather_stack(stacked)

            def body0(carry, xs):
                idx, cur = xs[0], list(xs[1:])
                h2 = pin_act(apply_layer(idx, cur, carry, kraw, extras))
                return h2, carry

            return jax.lax.scan(
                body0, h0, (jnp.arange(n_layers),) + tuple(full))
        first = gather([v[0] for v in stacked])
        # iteration k's xs slice carries layer k+1's shards (last wraps to 0:
        # one redundant tail gather keeps the loop homogeneous)
        rolled = [jnp.roll(v, -1, axis=0) for v in stacked]

        def body(carry, xs):
            idx, nxt = xs[0], list(xs[1:])
            h, cur = carry
            nxt_full = gather(nxt)  # layer idx+1, overlaps layer idx compute
            h2 = pin_act(apply_layer(idx, cur, h, kraw, extras))
            return (h2, nxt_full), h  # ys: layer k's INPUT activation

        (h, _), bounds = jax.lax.scan(
            body, (h0, first), (jnp.arange(n_layers),) + tuple(rolled))
        return h, bounds

    @jax.custom_vjp
    def run(h0, kraw, *rest):
        stacked, extras = rest[:n_cols], rest[n_cols:]
        h, _ = _fwd_scan(h0, kraw, stacked, extras)
        return h

    def run_fwd(h0, kraw, *rest):
        stacked, extras = rest[:n_cols], rest[n_cols:]
        h, bounds = _fwd_scan(h0, kraw, stacked, extras)
        return h, (kraw, bounds, stacked, extras)

    def run_bwd(res, g):
        kraw, bounds, stacked, extras = res

        def layer_vjp(idx, cur, h_in, dh):
            def relin(w_full, h):
                return apply_layer(idx, w_full, h, kraw, extras)

            _, vjp = jax.vjp(relin, cur, h_in)
            dw_full, dh_in = vjp(dh)
            return tuple(scatter(list(dw_full))), pin_act(dh_in)

        if not ahead:
            full = gather_stack(stacked)

            def body0(carry, xs):
                idx, h_in, cur = xs[0], xs[1], list(xs[2:])
                dws, dh_in = layer_vjp(idx, cur, h_in, carry)
                return dh_in, dws

            dx, dws = jax.lax.scan(
                body0, g, (jnp.arange(n_layers), bounds) + tuple(full),
                reverse=True)
        else:
            last = gather([v[n_layers - 1] for v in stacked])
            # iteration k's xs slice carries layer k-1's shards (k=0 wraps)
            rolled = [jnp.roll(v, 1, axis=0) for v in stacked]

            def body(carry, xs):
                idx, h_in, prev = xs[0], xs[1], list(xs[2:])
                dh, cur = carry
                prev_full = gather(prev)  # layer idx-1 overlaps idx's bwd
                dws, dh_in = layer_vjp(idx, cur, h_in, dh)
                return (dh_in, prev_full), dws

            (dx, _), dws = jax.lax.scan(
                body, (g, last),
                (jnp.arange(n_layers), bounds) + tuple(rolled), reverse=True)
        return (dx, _zero_cotangent(kraw)) + tuple(dws) + tuple(
            _zero_cotangent(e) for e in extras)

    run.defvjp(run_fwd, run_bwd)
    return run(x, key_raw, *tuple(stacked_vals), *tuple(extra_arrs))


def unrolled_layer_call(layer, x, args: tuple = (), kwargs: dict | None = None,
                        policy: str = "none"):
    """One layer applied to hidden-state ARRAY `x` with the remat policy as a
    per-layer `jax.checkpoint` region (the unrolled-loop counterpart of
    `scan_layer_stack`); embed/head stay outside the region by construction.
    """
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.parallel.train_step import functional_call

    kwargs = kwargs or {}
    params = layer.parameters()

    def one(hv, *param_vals):
        out = functional_call(layer, list(param_vals), (Tensor(hv),) + args,
                              kwargs=kwargs)
        return out._value if isinstance(out, Tensor) else out

    wrapped = remat_wrap(one, policy)
    from paddle_tpu.core.tensor import apply_op

    return apply_op(wrapped, Tensor(x) if not isinstance(x, Tensor) else x,
                    *params, name="remat_layer")
