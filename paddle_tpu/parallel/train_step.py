"""Compiled SPMD train step — the performance path.

Reference analog: the whole static-graph pipeline (to_static -> StandaloneExecutor
-> PirInterpreter, SURVEY §3.5) plus EagerReducer's fused-overlapped gradient
sync (reducer.cc:1093). TPU-native: ONE jitted XLA program computes
loss -> grads -> optimizer update with:
  - parameters/optimizer state living as device arrays between steps (donated,
    so updates are in-place in HBM),
  - shardings from the mesh: batch dim 0 over "dp"/"sharding", the
    SEQUENCE dim over "sep" (context parallelism), params over
    "mp" (from the `_mp_pspec` annotations the TP layers attach), optimizer
    state over "sharding"/"dp" for ZeRO,
  - XLA inserting + overlapping all collectives (grad psum over dp ≈ the
    reference's fused allreduce; state sharding ≈ reduce-scatter of ZeRO).
Dropout gets a per-step folded key threaded through the program so compiled
training is stochastically correct (the RNGStatesTracker analog under jit).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax._src import config as jax_config
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.autograd import tape as _tape
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet import rng as fleet_rng
from paddle_tpu.distributed.mesh import get_mesh
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.observability import scopes
from paddle_tpu.observability import tracing as obs_tracing

__all__ = ["CompiledTrainStep", "functional_call", "init_opt_states",
           "apply_optimizer_update"]

faults.register(
    "step.grads",
    "poison one training step (fire_check site in CompiledTrainStep): "
    "NaN-scales the first float batch leaf (NaN grads — the in-program "
    "health check catches it the SAME step and skips the update) or, for "
    "integer-only batches, the learning rate (params corrupted — caught "
    "on the NEXT step's non-finite loss; only rollback recovers)")


def _nan_poison(vals):
    """Chaos helper for the `step.grads` point: NaN-scale the first
    floating batch leaf. Returns (vals, poisoned?) — False means the batch
    has no float leaf (token ids) and the caller poisons the lr instead."""
    if isinstance(vals, dict):
        for k in sorted(vals):
            v = vals[k]
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
                out = dict(vals)
                out[k] = v * jnp.asarray(float("nan"), v.dtype)
                return out, True
        return vals, False
    out = list(vals)
    for i, v in enumerate(out):
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            out[i] = v * jnp.asarray(float("nan"), v.dtype)
            return tuple(out), True
    return vals, False


def _innermost_opt(opt):
    """Walk wrapper chains (HybridParallelOptimizer etc.) to the optimizer
    whose _state/_step_count feed state_dict()."""
    seen = set()
    while id(opt) not in seen:
        seen.add(id(opt))
        inner = opt.__dict__.get("_inner_opt")
        if inner is None:
            break
        opt = inner
    return opt


def sync_pipeline_states_to_optimizer(optimizer, states, embed_params,
                                      head_params, block_params, unstack,
                                      step_i):
    """Shared checkpoint-parity sync for the pipelined runtimes
    (PipelinedTrainStep / ZBH1PipelinedStep): flat [embed..., stacked-blocks
    ..., head...] states written into the INNERMOST optimizer's _state, with
    stacked block states split per layer via `unstack`."""
    opt = _innermost_opt(optimizer)
    ne = len(embed_params)
    nh = len(head_params)
    nb = len(states) - ne - nh
    for p, st in zip(embed_params, states[:ne]):
        opt._state[id(p)] = dict(st)
    for p, st in zip(head_params, states[ne + nb:]):
        opt._state[id(p)] = dict(st)
    for i, st in enumerate(states[ne:ne + nb]):
        flat = {k: unstack(v) for k, v in st.items()}
        for l, bp in enumerate(block_params):
            opt._state[id(bp[i])] = {k: v[l] for k, v in flat.items()}
    opt._step_count = step_i


def init_opt_states(optimizer, vals, params=None, block_params=None,
                    stack=None):
    """Per-array optimizer state, co-located with its (sharded) value —
    shared by the compiled pipeline runtimes.

    With `params`/`block_params`/`stack`, entries RESUME from a loaded
    checkpoint's optimizer._state instead of starting from zero moments:
    `params` aligns embed/head entries with their Parameter (None marks a
    stacked block column), `block_params[l][i]` is layer l's parameter behind
    stacked column i, and `stack` maps the per-layer state arrays into the
    runtime's stacked block layout (the inverse of its `_unstack`). Columns
    whose per-layer states are missing or mismatched re-init fresh — the same
    granularity as CompiledTrainStep._resume_states."""
    existing = getattr(optimizer, "_state", {}) if params is not None else {}
    states = []
    col_i = 0

    def _shapes_ok(st, v):
        # a stale-shaped moment (e.g. a resized embedding) must re-init
        # fresh, not explode later inside the optimizer update
        return all(tuple(np.shape(s)) in ((), tuple(v.shape))
                   for s in st.values())

    for idx, v in enumerate(vals):
        p = params[idx] if params is not None else None
        st = None
        if p is not None:
            saved = existing.get(id(p))
            if saved:
                st = dict(saved)
        elif params is not None and block_params is not None:
            col = [bp[col_i] for bp in block_params]
            col_i += 1
            sts = [existing.get(id(cp)) for cp in col]
            if any(s is not None for s in sts) and stack is not None:
                if (all(s is not None for s in sts)
                        and len({frozenset(s) for s in sts}) == 1):
                    try:
                        st = {k: stack([jnp.asarray(s[k]) for s in sts])
                              for k in sts[0]}
                    except (ValueError, TypeError):
                        import warnings

                        # heterogeneous per-layer shapes cannot stack —
                        # same warn-and-reinit contract as below
                        warnings.warn(
                            "pipeline resume: per-layer optimizer state "
                            "shapes are heterogeneous; reinitializing the "
                            "stacked entry's moments from zero")
                        st = None
                else:
                    import warnings

                    warnings.warn(
                        "pipeline resume: per-layer optimizer states are "
                        "incomplete or have mismatched keys; reinitializing "
                        "the stacked entry's moments from zero")
        if st is not None and not _shapes_ok(st, v):
            import warnings

            warnings.warn(
                "pipeline resume: restored optimizer state shapes do not "
                "match the parameter; reinitializing that entry's moments "
                "from zero")
            st = None
        if st is None:
            st = optimizer._init_state(Tensor(v))
        st = {k: jax.device_put(jnp.asarray(s), v.sharding)
              for k, s in st.items()}
        states.append(st)
    return states


def apply_optimizer_update(optimizer, params, grads, states, lr, step_i):
    """Pure (jit-safe) update loop over flat array lists: dtype-cast grads,
    honor the optimizer's grad_clip (global-norm / per-tensor norm / value,
    the nn.clip semantics on raw arrays), then optimizer._update per array.
    The single implementation behind PipelinedTrainStep and
    ZBH1PipelinedStep — schedule runtimes must not drift apart here."""
    new_p, new_s = [], []
    with scopes.scope("optimizer"):
        grads = [g.astype(p.dtype) if g.dtype != p.dtype else g
                 for p, g in zip(params, grads)]
        clip = getattr(optimizer, "_grad_clip", None)
        if clip is not None:
            grads = _clip_grads(clip, grads)
        for pv, gv, st in zip(params, grads, states):
            np_, ns_ = optimizer._update(pv, gv, st, lr, step_i)
            new_p.append(np_)
            new_s.append(ns_)
    return new_p, new_s


def _clip_grads(clip, grads):
    """nn.clip semantics (global norm / per-tensor norm / value) on raw
    arrays."""
    from paddle_tpu.nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                                    ClipGradByValue)

    if isinstance(clip, ClipGradByGlobalNorm):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in grads))
        f = jnp.where(gn > clip.clip_norm,
                      clip.clip_norm / jnp.maximum(gn, 1e-12), 1.0)
        return [g * f.astype(g.dtype) for g in grads]
    if isinstance(clip, ClipGradByNorm):
        out = []
        for g in grads:
            n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            f = jnp.where(n > clip.clip_norm,
                          clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
            out.append(g * f.astype(g.dtype))
        return out
    if isinstance(clip, ClipGradByValue):
        return [jnp.clip(g, clip.min, clip.max) for g in grads]
    return grads


def _param_pspec(p: Tensor, mesh: Mesh | None) -> PartitionSpec:
    spec = getattr(p, "_mp_pspec", None)
    if mesh is None or spec is None:
        return PartitionSpec()
    dims = []
    for s in spec:
        if s is not None and s in mesh.shape and mesh.shape[s] > 1:
            dims.append(s)
        else:
            dims.append(None)
    return PartitionSpec(*dims)


def _state_pspec(p_spec: PartitionSpec, state_val, axis: str | None, mesh: Mesh | None,
                 start_dim: int = 0):
    """ZeRO: shard optimizer state over `axis` on the FIRST dim that is not
    already mp-sharded and is divisible — an mp-sharded table (dim 0 over
    'mp') still gets its moments dp-sharded on dim 1, so per-device state is
    1/(mp*dp) of the total (the PS-scale sparse-table layout).

    start_dim: first dim eligible for the axis. Scan-stacked group columns
    pass 1 — their dim 0 is the LAYER axis the scan slices per iteration,
    and sharding it would make every iteration's state slice (and the grad
    accumulator the partitioner propagates it onto) a cross-device gather."""
    if mesh is None or axis is None or axis not in mesh.shape or mesh.shape[axis] <= 1:
        return p_spec
    dims = list(p_spec) + [None] * (state_val.ndim - len(list(p_spec)))
    if state_val.ndim == 0:
        return PartitionSpec()
    flat_axes = [a for entry in dims if entry
                 for a in (entry if isinstance(entry, tuple) else (entry,))]
    if axis not in flat_axes:  # zero-3 already shards params over `axis`
        for d in range(start_dim, state_val.ndim):
            if dims[d] is None and state_val.shape[d] % mesh.shape[axis] == 0:
                dims[d] = axis
                break
    return PartitionSpec(*dims[: state_val.ndim])


def _zero3_param_spec(spec: PartitionSpec, val, axis: str | None, mesh: Mesh | None):
    """ZeRO-3: persist the parameter itself sharded on dim 0 over `axis`
    (GSPMD all-gathers on use inside the step — the reference stage-3
    forward-pre-hook allgather, group_sharded_stage3.py:85)."""
    if (mesh is None or axis is None or axis not in mesh.shape
            or mesh.shape[axis] <= 1 or val.ndim == 0):
        return spec
    dims = list(spec) + [None] * (val.ndim - len(list(spec)))
    if dims[0] is None and axis not in dims and val.shape[0] % mesh.shape[axis] == 0:
        dims[0] = axis
        return PartitionSpec(*dims[: val.ndim])
    return spec


def _zero3_stacked_spec(spec: PartitionSpec, val, axis: str | None,
                        mesh: Mesh | None):
    """ZeRO-3 layout for a scan-stacked [L, ...] group column: shard the
    first free, divisible NON-layer dim over `axis` (dim 0 is the scan axis —
    sharding it would make the per-iteration layer slice a cross-device
    gather). Returns (spec, sharded?); the scan loop re-gathers per layer
    (scan_layers gather-ahead), so unlike `_zero3_param_spec` this is NOT a
    leave-it-to-GSPMD layout."""
    if (mesh is None or axis is None or axis not in mesh.shape
            or mesh.shape[axis] <= 1 or val.ndim <= 1):
        return spec, False
    dims = list(spec) + [None] * (val.ndim - len(list(spec)))
    flat_axes = [a for entry in dims if entry
                 for a in (entry if isinstance(entry, tuple) else (entry,))]
    if axis in flat_axes:
        return spec, False
    for d in range(1, val.ndim):
        if dims[d] is None and val.shape[d] % mesh.shape[axis] == 0:
            dims[d] = axis
            return PartitionSpec(*dims[: val.ndim]), True
    return spec, False


def host_memory_supported() -> bool:
    """True when the backend exposes a pinned-host memory space (TPU does;
    the CPU test backend does not — offload then degrades to device)."""
    try:
        dev = jax.local_devices()[0]
        return any(m.kind == "pinned_host" for m in dev.addressable_memories())
    except Exception:
        return False


def functional_call(model, params_vals: Sequence, args, kwargs=None, training=True,
                    method=None, params=None):
    """Run `model` with its parameters temporarily bound to `params_vals`
    (possibly tracers). All paddle_tpu ops are pure jax fns of Tensor._value,
    so ordinary Python execution under tracers IS the graph capture.
    `method` names an alternative entry point (e.g. "forward_features" for
    the fused-head protocol) instead of `model.__call__`. `params` restricts
    the binding to a subset of the model's parameters (scan-over-layers
    packing binds only the non-stacked ones; the stacked group arrives via
    the layer-execution context instead)."""
    kwargs = kwargs or {}
    params = model.parameters() if params is None else params
    old = [p._value for p in params]
    try:
        for p, v in zip(params, params_vals):
            p._set_value(v)
        t_args = [Tensor(a) if isinstance(a, jax.Array) else a for a in args]
        t_kwargs = {k: Tensor(v) if isinstance(v, jax.Array) else v
                    for k, v in kwargs.items()}
        fn = getattr(model, method) if method else model
        with _tape.no_grad():
            out = fn(*t_args, **t_kwargs)
        return out
    finally:
        for p, v in zip(params, old):
            p._set_value(v)


class CompiledTrainStep:
    """Compile (model, loss_fn, optimizer) into one sharded XLA program.

    batch_spec: PartitionSpec for each batch input (default: shard dim0 over
    every data-like axis present in the mesh).
    zero_axis: mesh axis for ZeRO sharding; None = off.
    zero_stage: 1/2 = optimizer state sharded over zero_axis (grad
      reduce-scatter is GSPMD's choice once the update is sharded); 3 = the
      parameters themselves are ALSO persisted sharded. With scan_layers the
      stacked decoder columns persist reduce-scattered on a non-layer dim
      and the scan loop gathers them back per layer; without scan packing
      (or for the embed/head outer params) GSPMD gathers on use.
    zero3_gather: 'ahead' (default, the `zero3_gather` flag) = double-
      buffered gather-ahead — layer k+1's weights all-gather while layer k
      computes and backward re-gathers + reduce-scatters grads, so at most
      2 layers of full weights are ever live; 'start' = all-gather the whole
      stack before the loop (the overlap-free baseline).
    offload_optimizer: place optimizer state in pinned host memory
      (reference sharding offload variants); requires backend host-memory
      support (TPU), silently stays in HBM otherwise.
    metrics_every: pacing for `step_async` — every k-th returned LossFuture
      comes pre-blocked (already finished, so reading it is free). k=1 (the
      `metrics_sync_every` flag default) keeps fully synchronous pacing;
      0 never blocks, leaving run-ahead bounded only by dispatch_window.
      None reads the flag. `__call__` itself never blocks on the loss.
    dispatch_window: max un-fetched steps in flight before dispatch blocks
      on the oldest loss (None reads the `async_dispatch_window` flag).
      Bounds async run-ahead so queued steps' batches can't OOM HBM.
    remat: selective-rematerialization policy — a string from
      paddle_tpu.parallel.scan_layers.REMAT_POLICIES
      (none|full|save_dots|save_nothing|offload_residuals), a bool
      (back-compat: True -> 'full', False -> 'none'), or None to read the
      `remat_policy` flag. Cooperating models (`layer_remat_capable`) get the
      policy applied PER LAYER, so the embed/fused-head/CE segment is never
      recomputed; other models fall back to the legacy whole-loss
      `jax.checkpoint` region (with the policy attached).
    fp8_policy: low-precision matmul policy (mirrors remat_policy):
      'none' | 'matmuls' | 'matmuls+head', or None to read the `fp8_policy`
      flag. 'matmuls' runs the model's F.linear projections through
      float8_e4m3 (gradients float8_e5m2) with DELAYED scaling: per-tensor
      amax histories live as an explicit fp8-state pytree threaded through
      the step exactly like optimizer state (discovered by one abstract
      trace on the first call; stacked [L, H] for callsites inside the
      lax.scan layer loop; checkpoint via fp8_state_dict/load_fp8_state).
      '+head' additionally quantizes the fused-CE head projection (softmax
      stats stay fp32). Composes with zero_axis ZeRO-1/2 (the amax state
      rides replicated next to its stack column); the zero_stage=3
      sharded-weights scan owns its vjp residuals and rejects fp8.
    grad_scaler: an amp.GradScaler for float16 training: the loss is
      scaled inside the program, gradients are unscaled in fp32, and a
      non-finite gradient skips the whole optimizer update (params AND
      moments keep their old values). The scaler's state machine is
      advanced from the per-step found_inf scalar WITHOUT breaking async
      dispatch: flags settle lazily as their device values become ready
      (drain() settles all), so the scale a queued step uses may lag by the
      in-flight window — the documented async-AMP semantics.
    anomaly_detector: in-program anomaly detection (docs/resilience.md):
      an `resilience.AnomalyDetector` (or True for a flag-configured one;
      None reads the `anomaly_detection` flag; False forces off). When on,
      the step computes a health scalar (non-finite loss or grads) INSIDE
      the program — an unhealthy step skips the whole optimizer update,
      exactly like the GradScaler found_inf path — and settles it into the
      detector lazily (only ready buffers are read), so `step_async`
      run-ahead never blocks on detection. The detector additionally flags
      host-side loss spikes (rolling median+MAD) and records/escalates per
      its policy; the resilience supervisor or Model.fit(resilience=) act
      on the escalations.
    collect_metrics: honest per-step telemetry (docs/observability.md):
      the step additionally returns a small metrics side-pytree — fp32
      loss, GLOBAL grad-norm (post-unscale), the found_inf/skip flag, and
      (with fp8) the amax watermark — as replicated device scalars that
      settle lazily on the host (`last_metrics()`, `settle_metrics()`);
      run-ahead is never broken by collection, and the output structure is
      stable so enabling it costs ONE compile, zero retraces. None reads
      the `step_telemetry` flag. `cost_analysis()`/`flops_per_step()`
      expose XLA's own cost model for the compiled step (what MFU gauges
      derive from).
    scan_layers: stack the model's `scan_group()` layer parameters along a
      leading layer axis OUTSIDE the program and run the stack as one
      `lax.scan` — HLO size and compile time become O(1) in depth. None reads
      the `scan_layers` flag. State-dict layout, per-layer optimizer resume,
      and `sync_params_to_model`/`sync_states_to_optimizer` round-trips are
      preserved (stacked arrays are split back per layer on sync).
    """

    def __init__(self, model, loss_fn: Callable, optimizer=None, mesh: Mesh | None = None,
                 batch_spec: PartitionSpec | None = None, zero_axis: str | None = None,
                 zero_stage: int = 1, offload_optimizer: bool = False,
                 donate: bool = True, remat: bool | str | None = None,
                 scan_layers: bool | None = None, seed: int = 0,
                 metrics_every: int | None = None,
                 dispatch_window: int | None = None,
                 zero3_gather: str | None = None,
                 fp8_policy: str | None = None, grad_scaler=None,
                 anomaly_detector=None, collect_metrics: bool | None = None):
        from paddle_tpu.amp.fp8 import normalize_fp8_policy
        from paddle_tpu.core.flags import flag
        from paddle_tpu.io.device_feed import DispatchWindow
        from paddle_tpu.parallel.scan_layers import normalize_remat

        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else get_mesh()
        self._params = model.parameters()
        self.remat_policy = normalize_remat(
            flag("remat_policy") if remat is None else remat)
        self.remat = self.remat_policy != "none"
        self.fp8_policy = normalize_fp8_policy(
            flag("fp8_policy") if fp8_policy is None else fp8_policy)
        self._fp8_hist_len = int(flag("fp8_amax_history_len"))
        self._fp8_states = None   # discovered on the first call
        self._fp8_layout = None
        self._scaler = (grad_scaler if grad_scaler is not None
                        and grad_scaler.is_enable() else None)
        self._pending_inf: list = []
        # in-program anomaly detection (docs/resilience.md): None reads the
        # anomaly_detection flag, True builds a flag-configured detector,
        # False forces OFF, an AnomalyDetector instance is used as-is
        from paddle_tpu.distributed.resilience.anomaly import AnomalyDetector
        if anomaly_detector is None:
            anomaly_detector = bool(flag("anomaly_detection"))
        if anomaly_detector is True:
            anomaly_detector = AnomalyDetector()
        self._anomaly_det = (anomaly_detector
                             if isinstance(anomaly_detector, AnomalyDetector)
                             else None)
        self._anomaly = self._anomaly_det is not None
        if (self._anomaly and self._scaler is not None
                and getattr(self._scaler, "_enable", True)
                and getattr(self._scaler, "_dynamic", True)
                and not getattr(self._anomaly_det, "tolerance_explicit",
                                False)
                and self._anomaly_det.nonfinite_tolerance == 0):
            # a dynamic loss scaler OVERFLOWS by design at every growth
            # interval (the skip + scale-halving is the recovery); only a
            # non-finite STREAK the scaler can't break is a real anomaly
            self._anomaly_det.nonfinite_tolerance = 2
        self._pending_health: list = []
        # honest step telemetry (docs/observability.md): the step returns a
        # metrics side-pytree; settled LAZILY like the health scalar, so
        # collection never breaks step_async run-ahead. None reads the
        # step_telemetry flag.
        self._telemetry = bool(flag("step_telemetry")
                               if collect_metrics is None
                               else collect_metrics)
        # layout of the packed per-step metrics vector (one readback/step)
        self._metric_keys = (["loss", "grad_norm", "skipped"]
                             + (["fp8_amax_max"]
                                if self.fp8_policy != "none" else []))
        # MoE models additionally report the summed load-balance aux loss
        # and dropped-token count through the same packed vector (the
        # layers' in-trace stats are read after the forward; under the
        # legacy whole-loss remat region those tracers are scoped to the
        # checkpoint, so collection is limited to remat-off steps)
        self._moe_layers = []
        if self._telemetry and not self.remat:
            from paddle_tpu.incubate.distributed.models.moe import (
                HeldExpertsMoE, MoELayer)

            self._moe_layers = [
                l for l in getattr(model, "sublayers", lambda: [])()
                if isinstance(l, (MoELayer, HeldExpertsMoE))]
        if self._moe_layers:
            self._metric_keys += ["moe_aux", "moe_dropped"]
        # layers that hold a share of their experts also count the
        # token-expert pairs routed to them and the held experts' load
        self._moe_load = any(hasattr(l, "step_stats") for l in self._moe_layers)
        if self._moe_load:
            self._metric_keys += ["moe_routed_slots", "moe_max_expert_load",
                                  "moe_mean_expert_load"]
        # summed over settled steps: host_counters()["moe"]
        self._moe_totals = {"steps": 0, "routed_slots": 0.0, "dropped": 0.0,
                            "max_expert_load": 0.0, "mean_expert_load": 0.0}
        self._pending_metrics: list = []
        self._last_metrics: dict | None = None
        self._prev_metric_wall: float | None = None
        self._lowered = None             # the build's lowering and
        self._executable = None          # compiled step (`_compile`)
        self._cost_analysis_cache = None
        self._layer_capable = bool(getattr(model, "layer_remat_capable", False))
        if scan_layers is None:
            scan_layers = bool(flag("scan_layers"))

        # ---- scan-over-layers packing --------------------------------------
        # outer params bind through functional_call as before; each column j
        # of the homogeneous scan_group becomes ONE stacked [L, ...] value
        self.scan_layers = False
        self._outer_params = self._params
        self._group_cols: list[list] = []  # [P][L] per-layer Parameters
        # packing requires BOTH halves of the cooperation protocol: a model
        # that only exposes scan_group() but never reads the layer-execution
        # context would trace its own (unbound) param values as constants and
        # train frozen weights. It also requires an ELEMENTWISE optimizer
        # update: Lamb/Lars compute a per-PARAMETER trust-ratio norm, which
        # over a stacked [L, ...] entry would couple all layers into one
        # ratio — silently different math than the unrolled run.
        if scan_layers and not self._layer_capable:
            scan_layers = False
        if scan_layers and optimizer is not None:
            from paddle_tpu.optimizer import Lamb, Lars

            if isinstance(_innermost_opt(optimizer), (Lamb, Lars)):
                scan_layers = False
        if scan_layers:
            sg = getattr(model, "scan_group", None)
            group = list(sg()) if callable(sg) else []
            if len(group) >= 2:
                per_layer = [list(l.parameters()) for l in group]
                n_per = len(per_layer[0])
                flat_group = [p for lp in per_layer for p in lp]
                own = {id(p) for p in self._params}
                ok = (n_per > 0
                      and all(len(lp) == n_per for lp in per_layer)
                      and all(not p.stop_gradient for p in flat_group)
                      and len({id(p) for p in flat_group}) == len(flat_group)
                      and all(id(p) in own for p in flat_group))
                if ok:
                    gid = {id(p) for p in flat_group}
                    self._outer_params = [p for p in self._params
                                          if id(p) not in gid]
                    self._group_cols = [[lp[j] for lp in per_layer]
                                        for j in range(n_per)]
                    self.scan_layers = True
        self._trainable = ([not p.stop_gradient for p in self._outer_params]
                           + [True] * len(self._group_cols))
        # routers whose correction bias the balancing rule moves
        # (`SigmoidGate.bias_update_rate`): the layer that routes leaves the
        # next value in the trace (`next_bias`), outside the gradient, and
        # the step stores it with the new parameters. Not under whole-loss
        # remat, where a value of the trace cannot leave the checkpoint.
        self._bias_gates = []
        if not self.remat:
            index = {id(p): i for i, p in enumerate(self._outer_params)}
            self._bias_gates = [
                (g, index[id(g.e_score_correction_bias)])
                for g in getattr(model, "sublayers", lambda: [])()
                if getattr(g, "bias_update_rate", 0.0) > 0.0
                and id(g.e_score_correction_bias) in index]
        self.zero_stage = zero_stage
        # offload needs the mesh-based shardings to stream states H2D in-step
        self._offload = (offload_optimizer and host_memory_supported()
                         and (mesh is not None or get_mesh() is not None))

        if batch_spec is None and self.mesh is not None:
            # batch dim 0 over the data axes, the SEQUENCE dim over 'sep'
            # (context parallelism) — shared with DeviceFeeder via
            # device_feed.default_batch_spec
            from paddle_tpu.io.device_feed import default_batch_spec

            batch_spec = default_batch_spec(self.mesh)
        self.batch_spec = batch_spec or PartitionSpec()
        # per-input trimmed shardings are computed ONCE per batch signature
        # (shapes+dtypes) and cached — not per step on the critical path
        from paddle_tpu.io.device_feed import BatchSpecCache

        self._spec_cache = BatchSpecCache(self.mesh, self.batch_spec)
        self.h2d_transfers = 0  # input leaves actually moved host->device
        self.metrics_every = int(flag("metrics_sync_every")
                                 if metrics_every is None else metrics_every)
        self._async_count = 0
        self._window = DispatchWindow(dispatch_window)
        self._builds = 0
        self._calls = 0

        # packed layout: [outer params..., one stacked array per group column]
        packed_vals = [p._value for p in self._outer_params]
        packed_specs = [_param_pspec(p, self.mesh) for p in self._outer_params]
        if self._group_cols:
            from paddle_tpu.parallel.scan_layers import stack_layer_vals

            n_layers = len(self._group_cols[0])
            packed_vals.extend(stack_layer_vals(
                [[col[l]._value for col in self._group_cols]
                 for l in range(n_layers)]))
            packed_specs.extend(
                PartitionSpec(None, *_param_pspec(col[0], self.mesh))
                for col in self._group_cols)
        self._zero3_scan_info = None
        if (zero_axis is not None and self.mesh is not None
                and zero_axis not in self.mesh.shape):
            import warnings

            # a typo'd axis must not silently train replicated at Z x the
            # provisioned parameter memory (axes of SIZE 1 stay silent —
            # build_mesh keeps them so specs are uniform across configs)
            warnings.warn(
                f"zero_axis={zero_axis!r} is not a mesh axis "
                f"({tuple(self.mesh.shape)}); ZeRO sharding is OFF")
        if zero_stage >= 3:
            n_outer = len(self._outer_params)
            packed_specs[:n_outer] = [
                _zero3_param_spec(s, v, zero_axis, self.mesh)
                for s, v in zip(packed_specs[:n_outer], packed_vals[:n_outer])
            ]
            if self._group_cols:
                # stacked columns persist reduce-scattered; the scan loop
                # re-gathers them per layer (gather-ahead by default) instead
                # of leaving the layout to GSPMD — see scan_layers.ScanShardInfo
                from paddle_tpu.parallel.scan_layers import ScanShardInfo

                mode = (flag("zero3_gather") if zero3_gather is None
                        else str(zero3_gather))
                cols, any_sharded = [], False
                for i, spec in enumerate(packed_specs[n_outer:]):
                    sharded, did = _zero3_stacked_spec(
                        spec, packed_vals[n_outer + i], zero_axis, self.mesh)
                    any_sharded = any_sharded or did
                    packed_specs[n_outer + i] = sharded
                    cols.append((PartitionSpec(*tuple(sharded)[1:]),
                                 PartitionSpec(*tuple(spec)[1:])))
                if (not any_sharded and zero_axis is not None
                        and zero_axis in self.mesh.shape
                        and self.mesh.shape[zero_axis] > 1):
                    import warnings

                    warnings.warn(
                        f"zero_stage=3: no stacked column has a free dim "
                        f"divisible by {zero_axis!r} "
                        f"(size {self.mesh.shape[zero_axis]}); the scan "
                        f"stack persists REPLICATED")
                if any_sharded:
                    if self.remat_policy not in ("none", "full"):
                        raise ValueError(
                            f"zero_stage=3 sharded-weights scan re-gathers "
                            f"and recomputes each layer in backward (its own "
                            f"'full'-grade schedule); remat policy "
                            f"{self.remat_policy!r} cannot apply to the "
                            f"sharded stack — use remat='none'/'full', or "
                            f"zero_stage<=2.")
                    self._zero3_scan_info = ScanShardInfo(
                        self.mesh, cols, mode=mode,
                        axis=zero_axis or "sharding",
                        act_spec=self.batch_spec)
        if self.fp8_policy != "none" and self._zero3_scan_info is not None:
            raise ValueError(
                "fp8_policy cannot compose with the zero_stage=3 "
                "sharded-weights scan: its custom vjp owns the scan "
                "residuals/cotangents and cannot thread the delayed-scaling "
                "amax state. Use zero_stage<=2 (optimizer-state sharding) "
                "with fp8_policy, or fp8_policy='none' with zero_stage=3.")
        self._param_specs = packed_specs
        self._key = jax.random.key(seed)
        # resume from a loaded optimizer's step count: Adam-style bias
        # correction must continue at t, not restart at 1 with warm moments
        self._step_i = int(getattr(optimizer, "_step_count", 0) or 0)

        # materialize params (sharded) + optimizer state. Outer params are
        # re-pointed at the placed arrays (shared buffers, as before); the
        # per-layer split of stacked group columns is DEFERRED to explicit
        # sync_params_to_model() calls — slicing here would keep a second
        # full copy of every layer's weights resident for the whole run
        # Without a mesh the arrays are COMMITTED where they already live
        # (host values: to the current place): the step's outputs are
        # committed, and a first call on uncommitted inputs would give step 2
        # another signature — one more trace and compile of the whole program.
        def commit(v):
            from paddle_tpu.core.device import current_jax_device

            return jax.device_put(
                v, getattr(v, "sharding", None) or current_jax_device())

        self._param_vals = []
        for v, spec in zip(packed_vals, self._param_specs):
            if self.mesh is not None:
                v = jax.device_put(v, NamedSharding(self.mesh, spec))
            else:
                v = commit(v)
            self._param_vals.append(v)
        for p, v in zip(self._outer_params,
                        self._param_vals[:len(self._outer_params)]):
            p._set_value(v)

        self._opt_states = None
        self._state_shardings = None
        if optimizer is not None:
            self._opt_states = []
            self._state_shardings = []
            n_outer_p = len(self._outer_params)
            for i, (pv, spec, st) in enumerate(
                    zip(self._param_vals, self._param_specs,
                        self._resume_states(optimizer))):
                st_sh = {}
                for k, v in st.items():
                    sp = _state_pspec(spec, v, zero_axis, self.mesh,
                                      start_dim=1 if i >= n_outer_p else 0)
                    sh = None
                    if self.mesh is not None:
                        if self._offload:
                            sh = NamedSharding(self.mesh, sp, memory_kind="pinned_host")
                        else:
                            sh = NamedSharding(self.mesh, sp)
                        v = jax.device_put(v, sh)
                    else:
                        v = commit(v)
                    st[k] = v
                    st_sh[k] = sh
                self._opt_states.append(st)
                self._state_shardings.append(st_sh)

        self._jitted = None
        self._donate = donate

    def _resume_states(self, optimizer):
        """Fresh per-packed-entry optimizer-state dicts: resumed from
        optimizer._state when a loaded checkpoint provides them (per-layer
        states are stacked for group columns; layers without a saved state
        get fresh moments individually, matching the unrolled path's
        per-param granularity), else freshly initialized."""
        existing = getattr(optimizer, "_state", {})
        n_outer = len(self._outer_params)
        for p, pv in zip(self._outer_params, self._param_vals[:n_outer]):
            p._set_value(pv)
            if p.stop_gradient:
                # frozen params (e.g. a LoRA-frozen base) never see the
                # update loop — keep no moments for them, so adapter
                # training's optimizer state is sized to the adapter
                yield {}
                continue
            yield dict(existing.get(id(p)) or optimizer._init_state(p))
        for col, sv in zip(self._group_cols, self._param_vals[n_outer:]):
            sts = [existing.get(id(p)) for p in col]
            if any(s is not None for s in sts):
                filled = [dict(s) if s is not None
                          else dict(optimizer._init_state(Tensor(sv[l])))
                          for l, s in enumerate(sts)]
                if len({frozenset(f) for f in filled}) == 1:
                    yield {k: jnp.stack([f[k] for f in filled])
                           for k in filled[0]}
                    continue
                import warnings

                warnings.warn(
                    "scan packing: per-layer optimizer states have "
                    "mismatched keys; reinitializing the stacked entry's "
                    "moments from zero")
            yield dict(optimizer._init_state(Tensor(sv)))

    # -- the pure step -------------------------------------------------------
    def _loss_of(self, param_vals, batch, key, fp8_states=None):
        counter = [0]

        def next_key():
            counter[0] += 1
            return jax.random.fold_in(key, counter[0])

        from contextlib import nullcontext

        from paddle_tpu.parallel.scan_layers import layer_execution

        n_outer = len(self._outer_params)
        stacked = list(param_vals[n_outer:]) if self._group_cols else None
        # cooperating models apply the policy per layer (embed/head/CE stay
        # outside every remat region); for others the context carries 'none'
        # and _step_fn wraps the whole loss in the legacy checkpoint region
        policy = self.remat_policy if self._layer_capable else "none"
        # delayed-scaling fp8: install the execute-mode session handing the
        # per-callsite amax states (tracers) out in discovery order. When
        # fp8_states is None (discovery itself, or fp8 off) no session is
        # installed here — discovery wraps this call in a record session.
        fp8_ctx = nullcontext()
        if self.fp8_policy != "none" and fp8_states is not None:
            from paddle_tpu.amp.fp8 import fp8_execution

            fp8_ctx = fp8_execution(self.fp8_policy, states=fp8_states,
                                    layout=self._fp8_layout,
                                    hist_len=self._fp8_hist_len)
        prev = fleet_rng._tls.active_key_fn
        fleet_rng._tls.active_key_fn = next_key
        try:
            with fp8_ctx:
                with layer_execution(policy, stacked,
                                     shard_info=self._zero3_scan_info):
                    if isinstance(batch, dict):
                        # named-batch protocol (packed batches: input_ids /
                        # labels / segment_ids / position_ids / ...): EVERY
                        # leaf is a model kwarg — labels included, so fused-
                        # head models compute the loss in-model — and
                        # `labels` also feeds loss_fn, preserving the
                        # (out, label) contract
                        out = functional_call(self.model,
                                              param_vals[:n_outer],
                                              (), kwargs=dict(batch),
                                              params=self._outer_params)
                        label = Tensor(batch["labels"])
                    else:
                        out = functional_call(self.model,
                                              param_vals[:n_outer],
                                              batch[:-1],
                                              params=self._outer_params)
                        label = Tensor(batch[-1])
                with scopes.scope("head"):
                    loss = self.loss_fn(out, label)
            return loss._value
        finally:
            fleet_rng._tls.active_key_fn = prev

    def _step_fn(self, param_vals, opt_states, batch, key, lr, step_i,
                 fp8_states=None, scaler_scale=None):
        fp8_on = self.fp8_policy != "none"
        fp8_in = list(fp8_states) if fp8_states is not None else []
        scaling = self._scaler is not None

        def run_loss(full_vals, fp8_s):
            return self._loss_of(full_vals, batch, key,
                                 fp8_states=fp8_s if fp8_on else None)

        if self.remat and not self._layer_capable:
            from paddle_tpu.parallel.scan_layers import remat_wrap

            # legacy whole-loss region for models that cannot scope remat
            # per layer themselves (the policy still applies, e.g. tagged
            # residuals offload under 'offload_residuals')
            run_loss = remat_wrap(run_loss, self.remat_policy)

        trainable_idx = [i for i, t in enumerate(self._trainable) if t]

        def moe_stats():
            # summed MoE stats over the layers' freshly-set in-trace
            # attributes (valid tracers of THIS forward)
            aux = jnp.zeros((), jnp.float32)
            dropped = jnp.zeros((), jnp.float32)
            for l in self._moe_layers:
                if l.l_aux is not None:
                    aux = aux + l.l_aux._value.astype(jnp.float32)
                if l.tokens_dropped is not None:
                    dropped = (dropped
                               + l.tokens_dropped._value.astype(jnp.float32))
            parts = [aux, dropped]
            if self._moe_load:
                # [pairs routed here, largest load, mean load, dropped] a
                # layer: pairs summed, the largest load over the layers,
                # the mean load averaged over them
                st = jnp.stack([l.step_stats._value.astype(jnp.float32)
                                for l in self._moe_layers
                                if getattr(l, "step_stats", None) is not None])
                parts += [jnp.sum(st[:, 0]), jnp.max(st[:, 1]),
                          jnp.mean(st[:, 2])]
            return jnp.stack(parts)

        def loss_all(train_vals, fp8_s):
            full = list(param_vals)
            for i, v in zip(trainable_idx, train_vals):
                full[i] = v
            # scopes name HLO metadata only (docs/observability.md): the
            # backward is `transpose(jvp(loss))` by JAX's own naming; `loss`
            # is on no list of parts (`observability.scopes`)
            with jax.named_scope("loss"):
                loss = run_loss(full, fp8_s)
            moe_vec = moe_stats() if self._moe_layers else None
            biases = [g.next_bias._value for g, _ in self._bias_gates]
            # float16 loss scaling happens INSIDE the differentiated fn so
            # the whole backward benefits; the aux output reports the
            # unscaled loss
            if scaling:
                return loss * scaler_scale.astype(loss.dtype), (
                    loss, moe_vec, biases)
            return loss, (loss, moe_vec, biases)

        train_vals = [param_vals[i] for i in trainable_idx]
        # the gradient of the loss w.r.t. the fp8 amax histories IS their
        # updated value (the fp8_dot custom-vjp's state-as-gradient
        # contract), so new_fp8 below is next step's state pytree
        (_, (loss, moe_vec, next_biases)), (grads, new_fp8) = jax.value_and_grad(
            loss_all, argnums=(0, 1), has_aux=True)(train_vals, fp8_in)

        # everything after the gradients is the update and its bookkeeping
        # (loss scaling, health, telemetry): one part of the step
        with scopes.scope("optimizer"):
            return self._update(param_vals, opt_states, grads, loss, moe_vec,
                                next_biases, fp8_in, new_fp8, lr, step_i,
                                scaler_scale, trainable_idx)

    def _update(self, param_vals, opt_states, grads, loss, moe_vec,
                next_biases, fp8_in, new_fp8, lr, step_i, scaler_scale,
                trainable_idx):
        """The step after its gradients: unscaling, the health flag, the
        telemetry vector, the optimizer's update and the gates' biases; the
        step's outputs."""
        fp8_on = self.fp8_policy != "none"
        scaling = self._scaler is not None
        found_inf = None
        if scaling:
            inv = (1.0 / scaler_scale).astype(jnp.float32)
            unscaled = []
            bad = jnp.zeros((), jnp.bool_)
            for g in grads:
                g32 = g.astype(jnp.float32) * inv
                bad = bad | ~jnp.isfinite(g32).all()
                unscaled.append(g32.astype(g.dtype))
            grads = unscaled
            found_inf = bad
        if self._anomaly:
            # the per-step HEALTH scalar (docs/resilience.md), riding the
            # found_inf convention: non-finite loss or ANY non-finite grad
            # marks the step unhealthy — the update below is skipped (a NaN
            # batch can never poison the params) and the scalar settles on
            # the host lazily, feeding the AnomalyDetector
            bad = (found_inf if found_inf is not None
                   else jnp.zeros((), jnp.bool_))
            if not scaling:
                for g in grads:
                    bad = bad | ~jnp.isfinite(g).all()
            found_inf = bad | ~jnp.isfinite(loss)
        if fp8_on and found_inf is not None:
            # a skipped step must not poison the amax histories: the
            # backward observed inf/nan amaxes, and delayed_scale of an
            # inf history is 0 -> NaN gradients on the NEXT step. Keep
            # the previous state, mirroring the params/moments skip.
            new_fp8 = jax.tree_util.tree_map(
                lambda old, new: jnp.where(found_inf, old, new),
                fp8_in, list(new_fp8))

        step_metrics = None
        if self._telemetry:
            # the honest per-step side output: tiny fp32 scalars riding the
            # program's outputs (no second dispatch, no host sync — readers
            # settle them lazily via settle_metrics), PACKED into one
            # [len(metric_keys)] vector so the host pays a single readback
            # per step, not one per metric. grad_norm is the GLOBAL norm
            # over every trainable leaf, post-unscale.
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads))
            parts = [
                loss.astype(jnp.float32),
                gn,
                (found_inf.astype(jnp.float32) if found_inf is not None
                 else jnp.zeros((), jnp.float32)),
            ]
            if fp8_on:
                leaves = jax.tree_util.tree_leaves(new_fp8)
                parts.append(
                    jnp.max(jnp.stack([jnp.max(l) for l in leaves]))
                    if leaves else jnp.zeros((), jnp.float32))
            if self._moe_layers:
                parts.extend(list(moe_vec))
            step_metrics = jnp.stack(parts)
        new_params = list(param_vals)
        new_states = list(opt_states) if opt_states is not None else None
        if self.optimizer is not None:
            offload = self._offload and self._state_shardings is not None

            def one_update(j, i, st):
                g = grads[j]
                if g.dtype != param_vals[i].dtype:
                    g = g.astype(param_vals[i].dtype)
                return self.optimizer._update(param_vals[i], g, st, lr,
                                              step_i)

            def streamed_state(i):
                st = opt_states[i]
                if offload:
                    # states live in pinned host memory; stream to HBM for
                    # the update (out_shardings stream the results back) —
                    # the reference's offload variants do the same H2D/D2H
                    # per step
                    st = {k: jax.device_put(v, self._state_shardings[i][k]
                                            .with_memory_kind("device"))
                          for k, v in st.items()}
                return st

            for j, i in enumerate(trainable_idx):
                st = streamed_state(i)
                np_, ns_ = one_update(j, i, st)
                if found_inf is not None:
                    # inf/nan grads (or an unhealthy anomaly-detected step)
                    # skip the WHOLE update: params and moments keep their
                    # previous values (GradScaler inf-skip semantics under
                    # jit). Per-tensor select, NOT one lax.cond around the
                    # loop: XLA fuses the select into the update kernel's
                    # epilogue (measured noise-level overhead), whereas the
                    # conditional's operand boundary materializes/copies
                    # every captured param+moment (measured ~10%/step).
                    np_ = jnp.where(found_inf, param_vals[i], np_)
                    ns_ = {k: jnp.where(found_inf, st[k], v)
                           for k, v in ns_.items()}
                new_params[i] = np_
                new_states[i] = ns_
        for (_, i), b in zip(self._bias_gates, next_biases):
            new_params[i] = (b if found_inf is None
                             else jnp.where(found_inf, param_vals[i], b))
        if fp8_on or scaling or self._anomaly:
            flag_out = (found_inf.astype(jnp.float32) if found_inf is not None
                        else jnp.zeros((), jnp.float32))
            if step_metrics is not None:
                return (loss, new_params, new_states, list(new_fp8),
                        flag_out, step_metrics)
            return loss, new_params, new_states, list(new_fp8), flag_out
        if step_metrics is not None:
            return loss, new_params, new_states, step_metrics
        return loss, new_params, new_states

    def _build(self):
        mesh = self.mesh
        extended = (self.fp8_policy != "none" or self._scaler is not None
                    or self._anomaly)
        if mesh is not None and self.optimizer is not None:
            pshard = [NamedSharding(mesh, s) for s in self._param_specs]
            sshard = self._state_shardings
            repl = NamedSharding(mesh, PartitionSpec())
            # the telemetry side output is ONE packed fp32 vector — always
            # replicated (its layout is static per configuration)
            mshard = repl if self._telemetry else None
            if extended:
                # amax histories are tiny ([H] / [L, H]) — they ride
                # replicated next to their (possibly sharded) stack column
                fshard = jax.tree_util.tree_map(
                    lambda _: repl, self._fp8_states or [])
                outs = (repl, pshard, sshard, fshard, repl)
                if mshard is not None:
                    outs = outs + (mshard,)
                self._jitted = jax.jit(
                    self._step_fn,
                    in_shardings=(pshard, sshard, None, None, None, None,
                                  fshard, None),
                    out_shardings=outs,
                    donate_argnums=(0, 1, 6) if self._donate else (),
                )
            else:
                outs = (repl, pshard, sshard)
                if mshard is not None:
                    outs = outs + (mshard,)
                self._jitted = jax.jit(
                    self._step_fn,
                    in_shardings=(pshard, sshard, None, None, None, None),
                    out_shardings=outs,
                    donate_argnums=(0, 1) if self._donate else (),
                )
        else:
            donate = (((0, 1, 6) if extended else (0, 1))
                      if self._donate else ())
            self._jitted = jax.jit(self._step_fn, donate_argnums=donate)

    # -- public --------------------------------------------------------------
    def __call__(self, *batch):
        """batch: (*inputs, label) as Tensors/arrays, OR one dict (the
        named-batch protocol a packed loader emits: every entry becomes a
        model kwarg — `labels` is required and also feeds loss_fn). Extra
        leaves like segment_ids/position_ids therefore ride along without
        positional-order coupling, get the same cached trimmed shardings as
        input_ids, and never retrace the step (the jit key is the batch
        pytree structure, stable across steps). Returns the loss as an
        UN-FETCHED Tensor: reading it (float()) is the device->host sync, so
        callers control how often dispatch is broken (`metrics_every`).
        Pre-placed inputs (a DeviceFeeder batch) whose sharding already
        matches skip the device_put entirely."""
        named = len(batch) == 1 and isinstance(batch[0], dict)
        if named and "labels" not in batch[0]:
            raise ValueError(
                "a dict batch must carry a 'labels' entry (it feeds both "
                f"the model and loss_fn); got keys {sorted(batch[0])}")
        # xprof's step view; the spans inside are the host's share of a step
        # (docs/observability.md)
        with jax.profiler.StepTraceAnnotation("train.call",
                                              step_num=self._step_i + 1):
            return self._call(batch, named)

    def _call(self, batch, named):
        with obs_tracing.span("train.place"):
            if named:
                keys = sorted(batch[0])
                flat, moved = self._spec_cache.place(
                    [batch[0][k] for k in keys])
                vals = dict(zip(keys, flat))
            else:
                vals, moved = self._spec_cache.place(batch)
            self.h2d_transfers += moved
        building = self._jitted is None
        if building:
            with obs_tracing.span("train.build", part="program"):
                if self.fp8_policy != "none" and self._fp8_states is None:
                    self._discover_fp8(vals)
                self._build()
            self._builds += 1
        with obs_tracing.span("train.dispatch", step=self._step_i + 1):
            loss = self._dispatch_step(vals, building)
        # bounded run-ahead: block on the loss of step N-window before
        # returning, so at most `window` compiled steps are queued on-device
        with obs_tracing.span("train.run_ahead_wait"):
            self._window.admit(loss)
        self._calls += 1
        if self.optimizer is not None:
            _innermost_opt(self.optimizer)._step_count = self._step_i
        return Tensor(loss)

    def _dispatch_step(self, vals, building: bool):
        """Everything between the placed batch and the enqueued step: the
        step's key and learning rate, the call of the compiled program (on
        the first call its trace and compile: `train.build`, `_compile`), and
        taking over its outputs."""
        self._step_i += 1
        self._key, sub = jax.random.split(self._key)
        lr = jnp.asarray(
            self.optimizer.get_lr() if self.optimizer is not None else 0.0, jnp.float32
        )
        if faults.fire_check("step.grads"):
            # chaos: poison THIS step — NaN grads via the first float batch
            # leaf, or (integer-only batches) a NaN lr corrupting the params
            vals, leaf_poisoned = _nan_poison(vals)
            if not leaf_poisoned:
                lr = jnp.asarray(float("nan"), jnp.float32)
        extended = (self.fp8_policy != "none" or self._scaler is not None
                    or self._anomaly)
        if extended:
            scale_arr = jnp.asarray(
                self._scaler._scale if self._scaler is not None else 1.0,
                jnp.float32)
            args = (self._param_vals, self._opt_states, vals, sub, lr,
                    jnp.asarray(self._step_i, jnp.int32),
                    self._fp8_states if self._fp8_states is not None
                    else [],
                    scale_arr)
        else:
            args = (self._param_vals, self._opt_states, vals, sub, lr,
                    jnp.asarray(self._step_i, jnp.int32))
        if building:
            self._compile(args)
        outs = self._jitted(*args)
        step_metrics = None
        if self._telemetry:
            step_metrics = outs[-1]
            outs = outs[:-1]
        if extended:
            (loss, self._param_vals, self._opt_states, new_fp8,
             found) = outs
            if self.fp8_policy != "none":
                self._fp8_states = new_fp8
            if self._scaler is not None:
                # settle the scaler state machine lazily: flags are read
                # only once their device value is ready, so async
                # dispatch never blocks here (drain() settles the rest)
                self._pending_inf.append(found)
                self._settle_scaler(block=False)
            if self._anomaly:
                # same lazy contract for the health scalar: the detector
                # only sees READY values, so step_async run-ahead is
                # never broken by detection
                self._pending_health.append((self._step_i, loss, found))
                self.settle_anomalies(block=False)
        else:
            loss, self._param_vals, self._opt_states = outs
        if step_metrics is not None:
            # same lazy contract as health/found_inf: the dict's device
            # scalars settle once ready (drain() settles all); the wall
            # time stamps host-side dispatch pacing
            self._pending_metrics.append(
                (self._step_i, step_metrics, time.perf_counter()))
            self.settle_metrics(block=False)
        return loss

    def _compile(self, args):
        """Trace, lower and compile the step for `args` before the call that
        donates them: the call then runs this executable (JAX's caches hand
        it over; the step is traced and compiled once). The lowering
        (`_lowered`: the StableHLO `chip_smoke.py` reads) and the executable
        (`cost_analysis()`) are kept; the executable's text maps each
        instruction to the named part of the model it belongs to
        (`observability.scopes.last_table()`, a table of strings: nothing of
        the step, its arrays or the executable).

        The persistent compile cache's key takes this program's metadata in:
        without it a step whose only change is where a scope lies is served
        with the old `op_name`s, and the table names the old parts."""
        with obs_tracing.span("train.build", part="compile"):
            self._lowered = self._jitted.lower(*args)
            with jax_config.compilation_cache_include_metadata_in_key(True):
                self._executable = self._lowered.compile()
        with obs_tracing.span("train.build", part="scopes"):
            scopes.publish(self._executable.as_text())

    def step_async(self, *batch):
        """Dispatch one step and return a LossFuture — the deferred-read
        handle for run-ahead training loops. Every `metrics_every`-th call
        blocks until its step finishes before returning (so the caller's
        periodic float() is free); with metrics_every=0 nothing ever blocks
        here and run-ahead is bounded only by the dispatch window.
        `drain()` before checkpointing/timing."""
        from paddle_tpu.io.device_feed import LossFuture

        f = LossFuture(self(*batch))
        self._async_count += 1
        if self.metrics_every and self._async_count % self.metrics_every == 0:
            f.block()
        return f

    def drain(self):
        """Block until every dispatched step has executed (and, with a
        grad_scaler / anomaly detector / telemetry, fold every outstanding
        found_inf, health flag and metrics pytree into their consumers)."""
        self._window.drain()
        if self._scaler is not None:
            self._settle_scaler(block=True)
        if self._anomaly:
            self.settle_anomalies(block=True)
        if self._telemetry:
            self.settle_metrics(block=True)

    # -- honest step telemetry (docs/observability.md) -----------------------
    def settle_metrics(self, block: bool = False):
        """Fold finished steps' metrics side-pytrees into `last_metrics`,
        in dispatch order. block=False only consumes values whose buffers
        are already ready — the non-blocking path runs after every
        dispatch, so step_async run-ahead is never broken by telemetry."""
        while self._pending_metrics:
            step_i, md, wall = self._pending_metrics[0]
            if not block:
                ready = getattr(md, "is_ready", None)
                if ready is not None and not ready():
                    break
            self._pending_metrics.pop(0)
            vals = np.asarray(md)  # ONE readback for the whole vector
            rec = dict(zip(self._metric_keys, (float(v) for v in vals)))
            rec["step"] = step_i
            # host-side pacing: wall time between consecutive dispatches
            # (the end-to-end step time a training loop actually feels,
            # input pipeline included — distinct from device step time)
            if self._prev_metric_wall is not None:
                rec["host_step_ms"] = round(
                    (wall - self._prev_metric_wall) * 1e3, 3)
            self._prev_metric_wall = wall
            self._last_metrics = rec
            if self._moe_load:
                tot = self._moe_totals
                tot["steps"] += 1
                tot["routed_slots"] += rec["moe_routed_slots"]
                tot["dropped"] += rec["moe_dropped"]
                # per-step readings, summed: divide by `steps` for the mean
                tot["max_expert_load"] += rec["moe_max_expert_load"]
                tot["mean_expert_load"] += rec["moe_mean_expert_load"]

    def last_metrics(self) -> dict | None:
        """The most recent SETTLED step's telemetry: {step, loss,
        grad_norm, skipped[, fp8_amax_max][, host_step_ms]} — None before
        the first settled step or with telemetry off."""
        if self._telemetry:
            self.settle_metrics(block=False)
        return self._last_metrics

    @property
    def collects_metrics(self) -> bool:
        return self._telemetry

    def host_counters(self) -> dict:
        """Cumulative host-side account of `__call__`, telemetry on or off:
        the number of calls (`steps`) and builds, and what JAX's compile log
        (`core.compile_cache`) holds for this class's program, process-wide;
        the host's seconds by part are the spans `train.place`,
        `train.dispatch`, `train.run_ahead_wait` and `train.build`
        (docs/observability.md). With telemetry on and a model that
        holds a share of its experts, `moe` sums over the SETTLED steps
        (read with the loss, never by a sync of their own) the token-expert
        pairs routed here (`moe.routed_slots`), the pairs past the rows
        laid out for them (`moe.dropped`) and each step's largest and mean
        load of a held expert (`moe.max_expert_load`,
        `moe.mean_expert_load`); `steps` is how many steps the sums hold."""
        from paddle_tpu.core.compile_cache import compile_totals

        out = {"steps": self._calls, "builds": self._builds,
               "compile": compile_totals("jit(_step_fn)")}
        if self._telemetry and self._moe_load:
            self.settle_metrics(block=False)
            out["moe"] = dict(self._moe_totals)
        return out

    def cost_analysis(self) -> dict:
        """XLA's own cost model for ONE compiled step (flops, bytes
        accessed, ...) — the honest FLOP count MFU derives from, replacing
        hand-counted formulas. Read from the executable the build compiled
        (`_compile`; cached; call OFF the hot path). Needs at least one
        executed step."""
        if self._cost_analysis_cache is not None:
            return self._cost_analysis_cache
        if self._executable is None:
            raise RuntimeError(
                "cost_analysis() needs at least one executed step (the "
                "step's executable is compiled by the first call)")
        ca = self._executable.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        self._cost_analysis_cache = dict(ca)
        return self._cost_analysis_cache

    def flops_per_step(self) -> float:
        """Total XLA-reported FLOPs of one step program (0.0 when the
        backend does not report them)."""
        return float(self.cost_analysis().get("flops", 0.0) or 0.0)

    # -- anomaly detection ---------------------------------------------------
    @property
    def anomaly_detector(self):
        return self._anomaly_det

    def settle_anomalies(self, block: bool = False):
        """Feed the AnomalyDetector from finished steps' device health
        scalars, in dispatch order. block=False only consumes values whose
        buffers are already ready — the non-blocking path __call__ runs
        after every dispatch; drain() settles the rest."""
        if self._anomaly_det is None:
            return
        while self._pending_health:
            step_i, loss, health = self._pending_health[0]
            if not block:
                ready = getattr(health, "is_ready", None)
                if ready is not None and not ready():
                    break
            self._pending_health.pop(0)
            self._anomaly_det.observe(step_i, float(loss), float(health))

    # -- fp8 delayed-scaling state -------------------------------------------
    def _discover_fp8(self, vals):
        """One abstract trace (jax.eval_shape — no compile, no FLOPs) of the
        loss under a recording fp8 session: counts the matmul callsites in
        call order, noting which sit inside the scanned layer group, and
        allocates the amax-history pytree — [H] per plain callsite, [L, H]
        per scanned one — placed replicated on the mesh."""
        from paddle_tpu.amp import fp8 as _fp8

        holder = {}

        def probe(pv, batch, key):
            with _fp8.fp8_recording(self.fp8_policy,
                                    self._fp8_hist_len) as rec:
                holder["rec"] = rec
                return self._loss_of(pv, batch, key)

        jax.eval_shape(probe, self._param_vals, vals, jax.random.key(0))
        rec = holder["rec"]
        self._fp8_layout = list(rec.layout)
        states = rec.init_states()
        if self.mesh is not None:
            repl = NamedSharding(self.mesh, PartitionSpec())
            states = jax.tree_util.tree_map(
                lambda v: jax.device_put(v, repl), states)
        self._fp8_states = states

    def fp8_state_dict(self):
        """The delayed-scaling amax state for checkpointing: the callsite
        layout plus the history arrays (host numpy). None before the first
        step has discovered the layout (or with fp8 off)."""
        if self._fp8_states is None:
            return None
        return {"layout": [tuple(e) for e in self._fp8_layout],
                "states": jax.tree_util.tree_map(
                    lambda v: np.asarray(v), self._fp8_states)}

    def load_fp8_state(self, snap):
        """Restore a fp8_state_dict() snapshot (before or after the first
        step); resuming then continues the uninterrupted amax trajectory."""
        if snap is None:
            return
        self._fp8_layout = [tuple(e) for e in snap["layout"]]
        states = snap["states"]
        if self.mesh is not None:
            repl = NamedSharding(self.mesh, PartitionSpec())
            states = jax.tree_util.tree_map(
                lambda v: jax.device_put(jnp.asarray(v), repl), states)
        else:
            states = jax.tree_util.tree_map(jnp.asarray, states)
        self._fp8_states = states

    def _settle_scaler(self, block: bool):
        """Advance the GradScaler state machine from finished steps' device
        found_inf flags, in dispatch order. block=False only consumes flags
        whose value is already on host-reachable (ready) buffers."""
        while self._pending_inf:
            f = self._pending_inf[0]
            if not block:
                ready = getattr(f, "is_ready", None)
                if ready is not None and not ready():
                    break
            self._pending_inf.pop(0)
            self._scaler._found_inf = bool(float(f) > 0.0)
            self._scaler.update()

    def sync_params_to_model(self):
        """Write the current device arrays back into the model's Tensors
        (checkpointing / eval interop). Scan-packed group columns are split
        back per layer, so state_dict layout is identical with scan on/off."""
        n_outer = len(self._outer_params)
        for p, v in zip(self._outer_params, self._param_vals[:n_outer]):
            p._set_value(v)
        for col, sv in zip(self._group_cols, self._param_vals[n_outer:]):
            for l, p in enumerate(col):
                p._set_value(sv[l])

    def sync_states_to_optimizer(self):
        """Write the in-program optimizer state back into optimizer._state so
        optimizer.state_dict() reflects trained moments (checkpoint parity).
        Targets the INNERMOST optimizer: wrappers delegate state_dict() there,
        and attribute assignment on a wrapper would only shadow it. Stacked
        group-column states are split back into per-layer entries."""
        if self.optimizer is None or self._opt_states is None:
            return
        opt = _innermost_opt(self.optimizer)
        n_outer = len(self._outer_params)
        for p, st in zip(self._outer_params, self._opt_states[:n_outer]):
            if not st:       # frozen param: no moments were ever allocated
                continue
            opt._state[id(p)] = dict(st)
        for col, st in zip(self._group_cols, self._opt_states[n_outer:]):
            for l, p in enumerate(col):
                opt._state[id(p)] = {k: v[l] for k, v in st.items()}
        opt._step_count = self._step_i

    # -- elastic checkpoint interface ----------------------------------------
    def _live_param_map(self):
        """id(parameter) -> its CURRENT device array. Group-column entries
        are lazy slices of the stacked [L, ...] arrays (async dispatch, no
        host sync); model buffers are not included (their Tensors are live)."""
        live = {}
        n_outer = len(self._outer_params)
        for p, v in zip(self._outer_params, self._param_vals[:n_outer]):
            live[id(p)] = v
        for col, sv in zip(self._group_cols, self._param_vals[n_outer:]):
            for l, p in enumerate(col):
                live[id(p)] = sv[l]
        return live

    def named_train_state(self):
        """(arrays, meta) for elastic checkpointing — the full training state
        under MESH-AGNOSTIC names, without a single host sync:

        * ``model/<state-dict name>`` — every model param (split per layer
          from the scan stack, so scan on/off saves look identical) + buffer,
          as live device arrays;
        * ``opt/<state-dict name>/<slot>`` — optimizer moments keyed by the
          owning parameter's NAME (not its position), so a pipeline runtime
          with a different parameter order resumes the same moments;
        * ``rng/key`` — the step's PRNG key data (the dropout trajectory
          continues bit-exactly across a resume);
        * meta: step count, fp8 callsite layout (+ ``fp8/<i>/<slot>`` amax
          histories in arrays), GradScaler scalars.

        The returned arrays may still be computing and WILL be invalidated by
        the next step's buffer donation — `checkpoint.elastic.capture` makes
        donation-safe device copies before the writer thread reads them.
        GradScaler scalars reflect the last SETTLED step (drain() first for
        exactness — the documented async-AMP lag)."""
        live = self._live_param_map()
        id2name = {}
        arrays = {}
        for name, t in self.model.state_dict().items():
            arrays[f"model/{name}"] = live.get(id(t), t._value)
            id2name[id(t)] = name
        if self._opt_states is not None:
            n_outer = len(self._outer_params)
            for p, st in zip(self._outer_params, self._opt_states[:n_outer]):
                name = id2name.get(id(p))
                if name is None:
                    continue
                for k, v in st.items():
                    arrays[f"opt/{name}/{k}"] = v
            for col, st in zip(self._group_cols,
                               self._opt_states[n_outer:]):
                for l, p in enumerate(col):
                    name = id2name.get(id(p))
                    if name is None:
                        continue
                    for k, v in st.items():
                        arrays[f"opt/{name}/{k}"] = v[l]
        arrays["rng/key"] = jax.random.key_data(self._key)
        meta = {"step": int(self._step_i)}
        if self._fp8_states is not None:
            meta["fp8_layout"] = [list(e) for e in self._fp8_layout]
            flat = jax.tree_util.tree_leaves(self._fp8_states)
            meta["fp8_leaves"] = len(flat)
            for i, leaf in enumerate(flat):
                arrays[f"fp8/{i:05d}"] = leaf
        if self._scaler is not None:
            meta["scaler"] = dict(self._scaler.state_dict())
        return arrays, meta

    def load_resume_extras(self, arrays, meta):
        """Restore the per-step extras a plain (model, optimizer) state-dict
        load cannot carry: RNG key, step counter, fp8 amax histories, and
        GradScaler scalars. Params/moments flow through
        `checkpoint.elastic.restore` BEFORE constructing the step (the
        constructor re-shards them for the target mesh)."""
        if "rng/key" in arrays:
            self._key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(arrays["rng/key"])))
        if "step" in meta:
            self._step_i = int(meta["step"])
            if self.optimizer is not None:
                _innermost_opt(self.optimizer)._step_count = self._step_i
        if meta.get("fp8_layout") is not None and self.fp8_policy != "none":
            n = int(meta.get("fp8_leaves", 0))
            leaves = [np.asarray(arrays[f"fp8/{i:05d}"]) for i in range(n)]
            # rebuild the callsite-state pytree: layout entries expand to one
            # {x,w,g} dict per callsite (scan entries carry k callsites)
            from paddle_tpu.amp.fp8 import STATE_KEYS

            # tree_leaves flattened each callsite dict in sorted-key order;
            # rebuild with the same ordering
            states, it = [], iter(leaves)
            for e in meta["fp8_layout"]:
                count = 1 if e[0] == "plain" else int(e[2])
                for _ in range(count):
                    states.append({k: next(it) for k in sorted(STATE_KEYS)})
            self.load_fp8_state({"layout": [tuple(e) for e in
                                            meta["fp8_layout"]],
                                 "states": states})
        if meta.get("scaler") is not None and self._scaler is not None:
            self._scaler.load_state_dict(dict(meta["scaler"]))

    @property
    def step_count(self):
        return self._step_i
