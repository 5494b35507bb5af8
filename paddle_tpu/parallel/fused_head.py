"""Head-stage loss fusion shared by the compiled runtimes.

The pipelined runtimes (PipelinedTrainStep 1F1B, ZBH1PipelinedStep) evaluate
`loss_fn(head(x), labels)` on the last stage. When the head ends in a plain
vocab projection and the loss is a recognizable hard-label softmax-CE, that
pair lowers to the chunked fused kernel
(`paddle_tpu.ops.pallas.fused_ce.fused_linear_cross_entropy_loss`): the
`[tokens, vocab]` logits never exist in forward or backward, and under a
bound "mp" axis the softmax stats reduce Megatron-style over the vocab
shards. Escape hatch: the `use_fused_head_loss` flag (read when the step
program is traced).

Fusion protocol (both conditions opt the head in):
  * the head layer implements ``forward_features(x)`` — everything it does
    BEFORE the final projection (`head(x) == head.lm_head(
    head.forward_features(x))` must hold) — and exposes that projection as
    ``head.lm_head`` (an `nn.Linear` or a `ColumnParallelLinear` that keeps
    its vocab shard local, i.e. gather_output=False under mp);
  * the loss_fn is an `nn.CrossEntropyLoss` in its fusable configuration, a
    `LlamaPretrainingCriterion`, or any callable carrying a
    ``_fused_ce_spec`` dict (keys: ignore_index, label_smoothing,
    reduction in {"mean", "sum", "mean_all"} — "mean" averages over
    non-ignored tokens like F.cross_entropy, "mean_all" over every token
    like `ParallelCrossEntropy(...).mean()`).
"""
from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor

__all__ = ["fused_ce_spec", "fused_head_spec", "fused_head_loss"]


def fused_ce_spec(loss_fn) -> dict | None:
    """The fused-CE config of `loss_fn(logits, labels)`, or None when the
    loss is not a recognizable hard-label softmax-CE."""
    spec = getattr(loss_fn, "_fused_ce_spec", None)
    if spec is not None:
        return dict(spec)
    from paddle_tpu.nn.layer.loss import CrossEntropyLoss

    if isinstance(loss_fn, CrossEntropyLoss):
        if (loss_fn.weight is None and not loss_fn.soft_label
                and loss_fn.use_softmax and loss_fn.axis == -1
                and loss_fn.use_fused is not False
                and loss_fn.reduction in ("mean", "sum")):
            return dict(ignore_index=loss_fn.ignore_index,
                        label_smoothing=loss_fn.label_smoothing,
                        reduction=loss_fn.reduction)
        return None
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    if isinstance(loss_fn, LlamaPretrainingCriterion):
        if loss_fn.parallel_ce is not None:
            # per-token parallel CE (ignored tokens contribute 0) then
            # .mean() over EVERY token — preserve that reduction exactly
            return dict(ignore_index=loss_fn.parallel_ce.ignore_index,
                        label_smoothing=0.0, reduction="mean_all")
        return dict(ignore_index=-100, label_smoothing=0.0, reduction="mean")
    return None


def fused_head_spec(head, loss_fn) -> dict | None:
    """The joint head+loss fusion spec for a (head layer, loss_fn) pair, or
    None when the pair must run the unfused `loss_fn(head(x), labels)`."""
    from paddle_tpu.core.flags import flag

    if not flag("use_fused_head_loss"):
        return None
    spec = fused_ce_spec(loss_fn)
    if spec is None:
        return None
    proj = getattr(head, "lm_head", None)
    if (getattr(head, "forward_features", None) is None or proj is None
            or getattr(proj, "weight", None) is None):
        return None
    from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import (
        ColumnParallelLinear)
    from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import MP_AXIS
    from paddle_tpu.distributed.mesh import mesh_axis_size

    if (isinstance(proj, ColumnParallelLinear) and proj.gather_output
            and mesh_axis_size(MP_AXIS) > 1):
        # gathered full-vocab output: the unfused loss sees [.., V] logits;
        # keep that path rather than re-deriving shard-local semantics
        return None
    return spec


def fused_head_loss(head, head_vals, x, labels, spec):
    """Scalar fp32 `loss_fn(head(x), labels)` via the chunked fused kernel,
    with `head_vals` temporarily bound as the head's parameters. x/labels
    are raw arrays; never builds the [tokens, vocab] logits."""
    from paddle_tpu.parallel.train_step import functional_call

    feat = functional_call(head, head_vals, (x,), method="forward_features")
    fv = feat._value if isinstance(feat, Tensor) else feat
    proj = head.lm_head

    def _bound_val(param):
        # the traced value bound to `param` (positional, like the swap
        # functional_call performs) — the layer attribute itself holds the
        # UNBOUND concrete value outside the call
        return next(v for p, v in zip(head.parameters(), head_vals)
                    if p is param)

    w = _bound_val(proj.weight)
    b = (_bound_val(proj.bias)
         if getattr(proj, "bias", None) is not None else None)
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy_loss

    lab = labels
    if lab.ndim == fv.ndim:
        lab = jnp.squeeze(lab, -1)
    flat = fv.reshape(-1, fv.shape[-1])
    labf = lab.reshape(-1)
    return fused_linear_cross_entropy_loss(
        flat, w, labf, b,
        ignore_index=spec.get("ignore_index", -100),
        label_smoothing=spec.get("label_smoothing", 0.0),
        z_loss=spec.get("z_loss", 0.0), mp_axis="auto",
        reduction=spec.get("reduction", "mean"))
