"""Pipeline-parallel training wrapper.

Reference parity: fleet/meta_parallel/pipeline_parallel.py — `PipelineParallel`
(:149), `train_batch` (:697), `forward_backward_pipeline` (1F1B, :459),
interleaved variants (:1010, :1831); p2p via batched isend/irecv
(pp_utils/p2p_communication.py:322).

TPU-native design: two execution paths with identical math:

1. **Eager path** (this file): micro-batch gradient accumulation — the exact
   arithmetic of 1F1B (same grads, same loss average) on the global-SPMD view.
   There is no host-visible bubble because XLA dispatch is async; per-stage
   device placement comes from the compiled path.
2. **Compiled path** (paddle_tpu.parallel.pipeline): the whole 1F1B schedule is
   ONE XLA program over the "pp" mesh axis — stages run concurrently on their
   mesh slice, activations hop stages via collective_permute over ICI (the
   batched-isend/irecv analog), microbatches streamed with lax.scan. Used by
   train_batch when `strategy.pipeline_configs['compile']` (default on TPU) and
   by dryrun_multichip.
"""
from __future__ import annotations

import warnings

import numpy as np

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.pp_layers import PipelineLayer
from paddle_tpu.nn.layer.layers import Layer

__all__ = ["PipelineParallel"]


class _Chain(Layer):
    """Sequential wrapper for the non-repeating prefix (embedding side) or
    suffix (head side) of a PipelineLayer's run list. Registers Layer members
    so functional_call sees their parameters; plain callables pass through."""

    def __init__(self, fns):
        super().__init__()
        self._fns = list(fns)
        for i, fn in enumerate(self._fns):
            if isinstance(fn, Layer):
                self.add_sublayer(f"seg_{i}", fn)

    def forward(self, x):
        for fn in self._fns:
            x = fn(*x) if isinstance(x, tuple) else fn(x)
        return x


def _param_sig(layer: Layer):
    return tuple((tuple(p.shape), str(p.dtype)) for p in layer.parameters())


def _decompose_run(run_function, num_stages):
    """Split a PipelineLayer run list into (prefix, homogeneous blocks, suffix)
    for the scanned compiled pipeline: the longest run of same-class layers
    with identical parameter signatures, length divisible by num_stages."""
    n = len(run_function)
    best = None  # (length, start, end)
    i = 0
    while i < n:
        fn = run_function[i]
        if not isinstance(fn, Layer) or not fn.parameters():
            i += 1
            continue
        sig = (type(fn), _param_sig(fn))
        j = i + 1
        while j < n:
            g = run_function[j]
            if not (isinstance(g, Layer) and (type(g), _param_sig(g)) == sig):
                break
            j += 1
        # distinct objects only (SharedLayerDesc reuses one instance)
        seen = set()
        uniq_end = i
        for k in range(i, j):
            if id(run_function[k]) in seen:
                break
            seen.add(id(run_function[k]))
            uniq_end = k + 1
        length = uniq_end - i
        length -= length % num_stages
        if length >= num_stages and (best is None or length > best[0]):
            best = (length, i, i + length)
        i = max(j, i + 1)
    if best is None:
        return None
    _, s, e = best
    return (_Chain(run_function[:s]), list(run_function[s:e]),
            _Chain(run_function[e:]))


class PipelineParallel:
    def __init__(self, layers: PipelineLayer, hcg, strategy):
        if not isinstance(layers, PipelineLayer):
            raise TypeError("PipelineParallel expects a PipelineLayer")
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = strategy.pipeline_configs if strategy is not None else {}
        self.accumulate_steps = int(cfg.get("accumulate_steps", 1))
        self.micro_batch_size = int(cfg.get("micro_batch_size", 1))
        self._compile_requested = bool(cfg.get("compile", True))
        self.num_stages = hcg.get_pipe_parallel_world_size()
        self.stage_id = hcg.get_stage_id()
        self.total_loss = None
        self._compiled_step = None
        self._compile_failed = False

    # -- compiled route ------------------------------------------------------
    def _maybe_compiled(self, optimizer):
        """Build (once) the compiled scanned-1F1B step from the PipelineLayer.
        Returns None — with a one-time warning — when the mesh has no pp axis
        or the layer list has no homogeneous block run to scan over."""
        if not self._compile_requested or self._compile_failed:
            return None
        if self._compiled_step is not None:
            return self._compiled_step
        from paddle_tpu.distributed.mesh import get_mesh

        mesh = get_mesh()
        if (mesh is None or "pp" not in mesh.shape
                or mesh.shape["pp"] != self.num_stages or self.num_stages < 2):
            self._compile_failed = True
            return None
        parts = _decompose_run(self._layers.run_function, self.num_stages)
        if parts is None:
            warnings.warn(
                "PipelineParallel: layer list has no homogeneous block run; "
                "falling back to eager micro-batch gradient accumulation")
            self._compile_failed = True
            return None
        embed, blocks, head = parts
        vpp = int(getattr(self._layers, "_num_virtual_pipeline_stages", 1) or 1)
        if len(blocks) % (self.num_stages * vpp) != 0:
            vpp = 1
        from paddle_tpu.parallel.pipeline import PipelinedTrainStep

        cfg = (self._strategy.pipeline_configs
               if self._strategy is not None else {})
        mode = str(cfg.get("schedule_mode", "1F1B")).upper().replace("-", "")
        if mode == "ZBH1":
            # the ZB-H1 runtime shards over pp only: mp/sep layers expect
            # LOCAL weight shards + axis collectives, which it does not
            # provide — fall back to the 1F1B program that honors them.
            # dp/sharding axes merely replicate (correct math, no dp
            # speedup): allow with a warning.
            breaking = [a for a in ("mp", "sep") if mesh.shape.get(a, 1) > 1]
            replicated = [a for a in ("dp", "sharding")
                          if mesh.shape.get(a, 1) > 1]
            if breaking:
                warnings.warn(
                    f"schedule_mode=ZB-H1 supports pp(+replicated dp) meshes "
                    f"only; axes {breaking} are active — using the compiled "
                    "1F1B schedule")
                mode = "1F1B"
            elif replicated:
                warnings.warn(
                    f"schedule_mode=ZB-H1 replicates the batch over "
                    f"{replicated} (correct math, no data-parallel speedup); "
                    "use 1F1B for dp scaling")
        try:
            if mode == "ZBH1":
                # executable zero-bubble schedule (reference
                # pipeline_zero_bubble.py): B/W split drives the tick table
                from paddle_tpu.parallel.zero_bubble import ZBH1PipelinedStep

                self._compiled_step = ZBH1PipelinedStep(
                    embed, blocks, head,
                    lambda out, lab: self._layers.loss(out, lab),
                    mesh=mesh, num_micro=self.accumulate_steps,
                    optimizer=optimizer)
            else:
                self._compiled_step = PipelinedTrainStep(
                    embed, blocks, head,
                    lambda out, lab: self._layers.loss(out, lab),
                    optimizer=optimizer, mesh=mesh,
                    num_micro=self.accumulate_steps,
                    remat=self._layers._recompute_interval > 0,
                    virtual_pp=vpp)
        except Exception as e:  # shape/mesh mismatch: degrade, don't die
            warnings.warn(
                f"PipelineParallel: compiled pipeline unavailable ({e}); "
                "using eager micro-batch gradient accumulation")
            self._compile_failed = True
            return None
        return self._compiled_step

    def _sync_from_compiled(self):
        if self._compiled_step is not None:
            self._compiled_step.sync_params_to_model()
            sync_states = getattr(self._compiled_step,
                                  "sync_states_to_optimizer", None)
            if sync_states is not None:
                sync_states()  # optimizer.state_dict() checkpoint parity

    # -- passthrough --------------------------------------------------------
    def __getattr__(self, name):
        return getattr(self.__dict__["_layers"], name)

    def __call__(self, *args, **kwargs):
        self._sync_from_compiled()
        return self._layers(*args, **kwargs)

    def parameters(self):
        self._sync_from_compiled()
        return self._layers.parameters()

    def state_dict(self, *a, **k):
        self._sync_from_compiled()
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        # loaded weights land on the layer Tensors: drop the compiled step so
        # it rebuilds (and re-shards) from the new values on next train_batch
        out = self._layers.set_state_dict(*a, **k)
        self._compiled_step = None
        self._compile_failed = False
        return out

    def train(self):
        self._layers.train()
        return self

    def eval(self):
        self._layers.eval()
        return self

    # -- scheduling ----------------------------------------------------------
    def _split_micro(self, data):
        from paddle_tpu.ops.manipulation import split

        x, y = data
        n = self.accumulate_steps
        if n == 1:
            return [(x, y)]
        xs = split(x, n, axis=0)
        ys = split(y, n, axis=0)
        return list(zip(xs, ys))

    def forward_backward_pipeline(self, data, scaler=None):
        """1F1B-equivalent gradient accumulation (reference :459). Grads of the
        micro-batches sum; loss reported as the mean over micro-batches."""
        micro = self._split_micro(data)
        total = None
        for x, y in micro:
            out = self._layers.forward(x)
            loss = self._layers.loss(out, y)
            if self.accumulate_steps > 1:
                loss = loss / self.accumulate_steps
            if scaler is not None:
                scaled = scaler.scale(loss)
                scaled.backward()
            else:
                loss.backward()
            total = loss if total is None else total + loss.detach()
        self.total_loss = total
        return total

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """reference: pipeline_parallel.py:697. Routes to the compiled scanned
        1F1B/VPP program (paddle_tpu.parallel.pipeline) when
        strategy.pipeline_configs['compile'] (default) and the mesh has a pp
        axis — the optimizer update runs inside the same XLA program. With
        schedule_mode='ZB-H1' (pp-only meshes) the zero-bubble schedule
        program computes loss+grads and a second jitted program applies the
        update. GradScaler implies a fp16 loss-scaling loop, which stays
        eager."""
        self._layers.train()
        if scaler is not None and self._compiled_step is not None:
            # switching to the eager scaler route mid-run: pull the compiled
            # weights back and retire the compiled step (eager updates would
            # otherwise diverge from its internal device arrays)
            self._sync_from_compiled()
            self._compiled_step = None
            self._compile_failed = True
        if scaler is None:
            compiled = self._maybe_compiled(optimizer)
            if compiled is not None:
                x, y = data
                loss = compiled(x, y)
                self.total_loss = loss
                optimizer.clear_grad()
                if lr_scheduler is not None:
                    lr_scheduler.step()
                return loss
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss=True):
        self._sync_from_compiled()
        self._layers.eval()
        from paddle_tpu.autograd.tape import no_grad

        micro = self._split_micro(data)
        total = None
        with no_grad():
            for x, y in micro:
                out = self._layers.forward(x)
                if compute_loss:
                    loss = self._layers.loss(out, y) / len(micro)
                    total = loss if total is None else total + loss
                else:
                    total = out
        return total
