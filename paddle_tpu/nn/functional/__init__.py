"""Functional NN ops (reference: python/paddle/nn/functional).

Every function lowers to XLA-friendly jax ops: convs via lax.conv_general_dilated
(MXU), attention via Pallas flash attention when available (reference analog:
nn/functional/flash_attention.py:147 wrapping third_party/flashattn), with an
XLA softmax fallback. NCHW layout is the API default (paddle convention); XLA
re-lays-out internally for the TPU.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dtype import to_jax_dtype
from paddle_tpu.core.flags import flag
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.lora import seam as _lora_seam
from paddle_tpu.ops.random_state import default_generator

__all__ = [
    # activations
    "relu", "relu6", "gelu", "sigmoid", "silu", "swish", "tanh", "softmax",
    "log_softmax", "leaky_relu", "elu", "selu", "celu", "hardshrink",
    "hardsigmoid", "hardswish", "hardtanh", "mish", "softplus", "softshrink",
    "softsign", "tanhshrink", "thresholded_relu", "log_sigmoid", "glu",
    "prelu", "rrelu", "maxout",
    # linear / embedding
    "linear", "embedding", "one_hot", "bilinear",
    # conv / pool
    "conv1d", "conv2d", "conv3d", "conv2d_transpose", "max_pool1d",
    "max_pool2d", "avg_pool1d", "avg_pool2d", "adaptive_avg_pool1d",
    "adaptive_avg_pool2d", "adaptive_max_pool2d", "unfold", "interpolate",
    "upsample", "pixel_shuffle",
    # norm
    "layer_norm", "batch_norm", "instance_norm", "group_norm", "rms_norm",
    "local_response_norm", "normalize",
    # dropout
    "dropout", "dropout2d", "alpha_dropout",
    # losses
    "cross_entropy", "parallel_cross_entropy", "fused_linear_cross_entropy",
    "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "cosine_similarity",
    "ctc_loss", "hinge_embedding_loss", "cosine_embedding_loss", "triplet_margin_loss",
    "label_smooth", "square_error_cost", "sigmoid_focal_loss",
    # attention
    "scaled_dot_product_attention", "flash_attention", "sequence_mask", "pad",
    "temperature_scaled_softmax",
]

from paddle_tpu.ops.manipulation import pad  # noqa: F401  (re-export)


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def _act(fn, name):
    def op(x, *args, **kwargs):
        return apply_op(lambda v: fn(v, *args, **kwargs), _t(x), name=name)

    op.__name__ = name
    return op


relu = _act(jax.nn.relu, "relu")
relu6 = _act(jax.nn.relu6, "relu6")
sigmoid = _act(jax.nn.sigmoid, "sigmoid")
silu = _act(jax.nn.silu, "silu")
swish = _act(jax.nn.silu, "swish")
tanh = _act(jnp.tanh, "tanh")
softplus = _act(jax.nn.softplus, "softplus")
softsign = _act(jax.nn.soft_sign, "softsign")
log_sigmoid = _act(jax.nn.log_sigmoid, "log_sigmoid")
mish = _act(jax.nn.mish, "mish")


def gelu(x, approximate=False):
    return apply_op(lambda v: jax.nn.gelu(v, approximate=approximate), _t(x), name="gelu")


def softmax(x, axis=-1, dtype=None):
    d = to_jax_dtype(dtype)

    def f(v):
        if d is not None:
            v = v.astype(d)
        return jax.nn.softmax(v, axis=axis)

    return apply_op(f, _t(x), name="softmax")


def temperature_scaled_softmax(x, temperature=1.0, axis=-1):
    return apply_op(lambda v: jax.nn.softmax(v / temperature, axis=axis), _t(x), name="softmax")


def log_softmax(x, axis=-1, dtype=None):
    d = to_jax_dtype(dtype)

    def f(v):
        if d is not None:
            v = v.astype(d)
        return jax.nn.log_softmax(v, axis=axis)

    return apply_op(f, _t(x), name="log_softmax")


def leaky_relu(x, negative_slope=0.01):
    return apply_op(lambda v: jax.nn.leaky_relu(v, negative_slope), _t(x), name="leaky_relu")


def elu(x, alpha=1.0):
    return apply_op(lambda v: jax.nn.elu(v, alpha), _t(x), name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return apply_op(
        lambda v: scale * jnp.where(v > 0, v, alpha * jnp.expm1(v)), _t(x), name="selu"
    )


def celu(x, alpha=1.0):
    return apply_op(lambda v: jax.nn.celu(v, alpha), _t(x), name="celu")


def hardshrink(x, threshold=0.5):
    return apply_op(
        lambda v: jnp.where(jnp.abs(v) > threshold, v, 0.0), _t(x), name="hardshrink"
    )


def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return apply_op(
        lambda v: jnp.clip(slope * v + offset, 0.0, 1.0), _t(x), name="hardsigmoid"
    )


def hardswish(x):
    return apply_op(lambda v: v * jnp.clip(v + 3.0, 0.0, 6.0) / 6.0, _t(x), name="hardswish")


def hardtanh(x, min=-1.0, max=1.0):
    return apply_op(lambda v: jnp.clip(v, min, max), _t(x), name="hardtanh")


def softshrink(x, threshold=0.5):
    return apply_op(
        lambda v: jnp.where(v > threshold, v - threshold, jnp.where(v < -threshold, v + threshold, 0.0)),
        _t(x), name="softshrink",
    )


def tanhshrink(x):
    return apply_op(lambda v: v - jnp.tanh(v), _t(x), name="tanhshrink")


def thresholded_relu(x, threshold=1.0):
    return apply_op(lambda v: jnp.where(v > threshold, v, 0.0), _t(x), name="thresholded_relu")


def glu(x, axis=-1):
    def f(v):
        a, b = jnp.split(v, 2, axis=axis)
        return a * jax.nn.sigmoid(b)

    return apply_op(f, _t(x), name="glu")


def prelu(x, weight):
    return apply_op(
        lambda v, w: jnp.where(v > 0, v, _reshape_prelu(w, v) * v), _t(x), _t(weight), name="prelu"
    )


def _reshape_prelu(w, v):
    if w.size == 1:
        return w.reshape(())
    shape = [1] * v.ndim
    shape[1] = w.size
    return w.reshape(shape)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True):
    if not training:
        return apply_op(lambda v: jnp.where(v >= 0, v, v * (lower + upper) / 2), _t(x), name="rrelu")
    key = default_generator.next_key()

    def f(v):
        slope = jax.random.uniform(key, v.shape, v.dtype, lower, upper)
        return jnp.where(v >= 0, v, v * slope)

    return apply_op(f, _t(x), name="rrelu")


def maxout(x, groups, axis=1):
    def f(v):
        shape = list(v.shape)
        c = shape[axis]
        shape[axis] = c // groups
        shape.insert(axis + 1, groups)
        return jnp.max(v.reshape(shape), axis=axis + 1)

    return apply_op(f, _t(x), name="maxout")


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------

# linear's collaborators, bound once on first call instead of re-imported
# per projection per decode step (this seam is the per-token hot path)
_fp8 = None
_prec = None


def _bind_linear_deps():
    global _fp8, _prec
    from paddle_tpu.amp import fp8 as fp8_mod
    from paddle_tpu.ops.linalg import _prec as prec_fn

    _fp8 = fp8_mod
    _prec = prec_fn


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b; W is [in, out] (paddle convention, nn/functional/common.py).

    Under an active fp8 session (`CompiledTrainStep(fp8_policy=...)`, the
    pipelined runtimes, or `amp.fp8_autocast`) the matmul runs through
    float8_e4m3 with e5m2 gradients — the hot-path seam the fp8 policy
    hooks (paddle_tpu.amp.fp8).

    This is also the LoRA dispatch seam (paddle_tpu.lora.seam): when this
    weight has attached train-mode A/B factors, or a serving AdapterStore
    binding is active inside the traced program, the rank-r delta is added
    here — every projection layer routes through this one function, so no
    model rewrite is needed to adapt it."""
    if _fp8 is None:
        _bind_linear_deps()
    xt, wt = _t(x), _t(weight)
    if _fp8.linear_fp8_enabled(xt._value, wt._value):
        return _fp8.fp8_linear(xt, wt, None if bias is None else _t(bias))
    if _lora_seam.active():
        sb = _lora_seam.serve_binding()
        if sb is not None:
            pool = sb.pools.get(id(weight))
            if pool is not None:
                a_pool, b_pool = pool

                def f_serve(v, w, *rest):
                    y = jnp.matmul(v, w, precision=_prec())
                    d = _lora_seam.serve_delta(v, a_pool, b_pool, sb)
                    y = y + d.astype(y.dtype)
                    return y + rest[0] if rest else y

                args = (xt, wt) if bias is None else (xt, wt, _t(bias))
                return apply_op(f_serve, *args, name="linear")
        entry = _lora_seam.train_lookup(id(weight))
        if entry is not None:
            s = entry.scale

            def f_train(v, w, a, b2, *rest):
                y = jnp.matmul(v, w, precision=_prec())
                d = jnp.matmul(jnp.matmul(v, a, precision=_prec()), b2,
                               precision=_prec())
                y = y + (s * d).astype(y.dtype)
                return y + rest[0] if rest else y

            args = (xt, wt, _t(entry.A), _t(entry.B))
            if bias is not None:
                args = args + (_t(bias),)
            return apply_op(f_train, *args, name="linear")
    if bias is None:
        return apply_op(lambda v, w: jnp.matmul(v, w, precision=_prec()), xt, wt, name="linear")
    return apply_op(
        lambda v, w, b: jnp.matmul(v, w, precision=_prec()) + b,
        xt, wt, _t(bias), name="linear",
    )


def embedding(x, weight, padding_idx=None, sparse=False):
    def f(ids, w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply_op(f, _t(x), _t(weight), name="embedding")


def one_hot(x, num_classes):
    from paddle_tpu.ops.creation import one_hot as _oh

    return _oh(x, num_classes)


def bilinear(x1, x2, weight, bias=None):
    def f(a, b, w):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        return out

    out = apply_op(f, _t(x1), _t(x2), _t(weight), name="bilinear")
    if bias is not None:
        out = out + _t(bias)
    return out


# ---------------------------------------------------------------------------
# convolution / pooling
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(i) for i in v)
    return (int(v),) * n


def _conv_nd(x, weight, bias, stride, padding, dilation, groups, nd, data_format):
    strides = _pair(stride, nd)
    dils = _pair(dilation, nd)
    if isinstance(padding, str):
        pad_cfg = padding.upper()  # SAME / VALID
    else:
        p = _pair(padding, nd) if not (isinstance(padding, (list, tuple)) and isinstance(padding[0], (list, tuple))) else padding
        pad_cfg = [(int(pi), int(pi)) for pi in p] if not isinstance(p[0], tuple) else p
    chan = "NCHW"[: 2 + nd] if nd == 2 else ("NCH" if nd == 1 else "NCDHW")
    if nd == 1:
        dn = jax.lax.conv_dimension_numbers(x._value.shape, weight._value.shape, ("NCH", "OIH", "NCH"))
    elif nd == 2:
        dn = jax.lax.conv_dimension_numbers(x._value.shape, weight._value.shape, ("NCHW", "OIHW", "NCHW"))
    else:
        dn = jax.lax.conv_dimension_numbers(x._value.shape, weight._value.shape, ("NCDHW", "OIDHW", "NCDHW"))

    def f(v, w, *maybe_b):
        out = jax.lax.conv_general_dilated(
            v, w, window_strides=strides, padding=pad_cfg,
            rhs_dilation=dils, dimension_numbers=dn, feature_group_count=groups,
            preferred_element_type=None,
        )
        if maybe_b:
            b = maybe_b[0]
            out = out + b.reshape((1, -1) + (1,) * nd)
        return out

    args = (x, weight) if bias is None else (x, weight, bias)
    return apply_op(f, *[_t(a) for a in args], name=f"conv{nd}d")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL"):
    return _conv_nd(_t(x), _t(weight), bias, stride, padding, dilation, groups, 1, data_format)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW"):
    if data_format == "NHWC":
        x = _t(x).transpose([0, 3, 1, 2])
        out = _conv_nd(x, _t(weight), bias, stride, padding, dilation, groups, 2, "NCHW")
        return out.transpose([0, 2, 3, 1])
    return _conv_nd(_t(x), _t(weight), bias, stride, padding, dilation, groups, 2, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW"):
    return _conv_nd(_t(x), _t(weight), bias, stride, padding, dilation, groups, 3, data_format)


def _group_transpose_kernel(w, groups, nd):
    """Paddle transpose-conv kernel (Cin, Cout/g, k...) -> XLA grouped 'IO'
    layout (Cin/g, Cout, k...): split Cin into g groups, fold the group axis
    into the output-feature dim (group-major, matching XLA's grouped-conv
    output partitioning). Identity reshape for groups == 1."""
    if groups == 1:
        return w
    cin, coutg = w.shape[0], w.shape[1]
    spatial = w.shape[2:]
    w = w.reshape((groups, cin // groups, coutg) + spatial)
    w = jnp.moveaxis(w, 0, 1)  # (Cin/g, g, Cout/g, k...)
    return w.reshape((cin // groups, groups * coutg) + spatial)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     dilation=1, groups=1, output_size=None, data_format="NCHW"):
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 2,
                              "conv2d_transpose")


def _pool(x, kernel, stride, padding, nd, reducer, init, data_format, count_include_pad=True, ceil_mode=False):
    ks = _pair(kernel, nd)
    st = _pair(stride if stride is not None else kernel, nd)
    pd = _pair(padding, nd)
    channels_last = data_format in ("NHWC", "NDHWC", "NLC")
    xv = x._value if isinstance(x, Tensor) else x
    sp = tuple(xv.shape[1:1 + nd] if channels_last else xv.shape[2:2 + nd])
    if ceil_mode:
        osp = [-(-(sp[d] + 2 * pd[d] - ks[d]) // st[d]) + 1 for d in range(nd)]
        # torch/paddle rule: the last window must start inside input+left-pad
        osp = [o - 1 if (o - 1) * st[d] >= sp[d] + pd[d] else o
               for d, o in enumerate(osp)]
    else:
        osp = [(sp[d] + 2 * pd[d] - ks[d]) // st[d] + 1 for d in range(nd)]
    # right padding so exactly osp windows exist; the part beyond the declared
    # pd is ceil-mode overhang (never counted in avg divisors)
    rp = [max((osp[d] - 1) * st[d] + ks[d] - sp[d] - pd[d], 0)
          for d in range(nd)]
    sp_pads = tuple((pd[d], rp[d]) for d in range(nd))
    if channels_last:
        window = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
        pads = ((0, 0),) + sp_pads + ((0, 0),)
        slicer = ((slice(None),) + tuple(slice(0, o) for o in osp)
                  + (slice(None),))
        base_pads = ((0, 0),) + tuple((pd[d], pd[d]) for d in range(nd)) + ((0, 0),)
        extra_pads = (((0, 0),) + tuple((0, max(rp[d] - pd[d], 0)) for d in range(nd))
                      + ((0, 0),))
    else:
        window = (1, 1) + ks
        strides = (1, 1) + st
        pads = ((0, 0), (0, 0)) + sp_pads
        slicer = ((slice(None), slice(None))
                  + tuple(slice(0, o) for o in osp))
        base_pads = ((0, 0), (0, 0)) + tuple((pd[d], pd[d]) for d in range(nd))
        extra_pads = ((0, 0), (0, 0)) + tuple((0, max(rp[d] - pd[d], 0))
                                              for d in range(nd))

    def f(v):
        if reducer == "max":
            return jax.lax.reduce_window(
                v, -jnp.inf, jax.lax.max, window, strides, pads)[slicer]
        s = jax.lax.reduce_window(v, 0.0, jax.lax.add, window, strides, pads)[slicer]
        if count_include_pad and not ceil_mode:
            return s / float(np.prod(ks))
        if count_include_pad:
            # divisor counts the declared zero-padding but not ceil overhang
            ones = jnp.pad(jnp.ones_like(v), base_pads, constant_values=1.0)
            cnt = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, window, strides, extra_pads)[slicer]
        else:
            cnt = jax.lax.reduce_window(
                jnp.ones_like(v), 0.0, jax.lax.add, window, strides, pads)[slicer]
        return s / cnt

    return apply_op(f, _t(x), name=f"{reducer}_pool{nd}d")


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW"):
    if return_mask:
        if data_format != "NCHW":
            raise ValueError(
                "return_mask=True requires data_format='NCHW' (reference "
                "paddle.nn.functional.max_pool2d contract)")
        return _max_pool_with_index_nd(x, kernel_size, stride, padding, 2,
                                       ceil_mode=ceil_mode)
    return _pool(x, kernel_size, stride, padding, 2, "max", -np.inf, data_format, ceil_mode=ceil_mode)


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False):
    if return_mask:
        return _max_pool_with_index_nd(x, kernel_size, stride, padding, 1,
                                       ceil_mode=ceil_mode)
    return _pool(x, kernel_size, stride, padding, 1, "max", -np.inf, "NCL", ceil_mode=ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW"):
    return _pool(x, kernel_size, stride, padding, 2, "avg", 0.0, data_format,
                 count_include_pad=not exclusive or padding == 0,
                 ceil_mode=ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, 1, "avg", 0.0, "NCL",
                 count_include_pad=not exclusive or padding == 0,
                 ceil_mode=ceil_mode)


def _adaptive_bin_matrix(in_size: int, out_size: int):
    """(out_size, in_size) row-averaging matrix: row i averages the adaptive
    bin [floor(i*in/out), ceil((i+1)*in/out)) — torch/paddle bin semantics."""
    m = np.zeros((out_size, in_size), np.float32)
    for i in range(out_size):
        lo = (i * in_size) // out_size
        hi = -(-((i + 1) * in_size) // out_size)  # ceil div
        m[i, lo:hi] = 1.0 / (hi - lo)
    return m


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    os = _pair(output_size)
    x = _t(x)
    if data_format == "NCHW":
        h, w = x._value.shape[2], x._value.shape[3]
    else:
        h, w = x._value.shape[1], x._value.shape[2]
    # _pool assumes NC-leading windows, so the divisible fast path is
    # NCHW-only; NHWC always takes the einsum path
    if data_format == "NCHW" and h % os[0] == 0 and w % os[1] == 0:
        return _pool(x, (h // os[0], w // os[1]), (h // os[0], w // os[1]), 0, 2, "avg", 0.0, data_format)
    # non-divisible bins: contract with per-axis averaging matrices — two
    # skinny MXU matmuls instead of 16 gather/slice reductions
    ah = _adaptive_bin_matrix(h, os[0])
    aw = _adaptive_bin_matrix(w, os[1])

    def f(v):
        if data_format == "NCHW":
            return jnp.einsum("nchw,oh,pw->ncop", v, ah, aw,
                              preferred_element_type=v.dtype)
        return jnp.einsum("nhwc,oh,pw->nopc", v, ah, aw,
                          preferred_element_type=v.dtype)

    return apply_op(f, x, name="adaptive_avg_pool2d")


def adaptive_avg_pool1d(x, output_size):
    x = _t(x)
    l = x._value.shape[2]
    os = int(output_size)
    if l % os == 0:
        return _pool(x, l // os, l // os, 0, 1, "avg", 0.0, "NCL")
    a = _adaptive_bin_matrix(l, os)

    def f(v):
        return jnp.einsum("ncl,ol->nco", v, a, preferred_element_type=v.dtype)

    return apply_op(f, x, name="adaptive_avg_pool1d")


def adaptive_max_pool2d(x, output_size, return_mask=False):
    os = _pair(output_size)
    x = _t(x)
    h, w = x._value.shape[2], x._value.shape[3]
    if h % os[0] == 0 and w % os[1] == 0:
        k = (h // os[0], w // os[1])
        if return_mask:
            return _max_pool_with_index_nd(x, k, k, 0, 2)
        return _pool(x, k, k, 0, 2, "max", -np.inf, "NCHW")

    def bins(size, out):
        return [((i * size) // out, -(-((i + 1) * size) // out)) for i in range(out)]

    hb, wb = bins(h, os[0]), bins(w, os[1])

    def f(v):
        rows = [jnp.stack([v[:, :, hl:hh, wl:wh].max(axis=(2, 3))
                           for (wl, wh) in wb], axis=-1)
                for (hl, hh) in hb]
        return jnp.stack(rows, axis=-2)

    def f_mask(v):
        outs, idxs = [], []
        for (hl, hh) in hb:
            row_o, row_i = [], []
            for (wl, wh) in wb:
                patch = v[:, :, hl:hh, wl:wh]
                bw = wh - wl
                flatp = patch.reshape(patch.shape[0], patch.shape[1], -1)
                am = jnp.argmax(flatp, axis=-1)
                row_o.append(jnp.max(flatp, axis=-1))
                # local bin argmax -> global flat h*w index (unpool contract)
                row_i.append((hl + am // bw) * w + (wl + am % bw))
            outs.append(jnp.stack(row_o, axis=-1))
            idxs.append(jnp.stack(row_i, axis=-1))
        return (jnp.stack(outs, axis=-2),
                jnp.stack(idxs, axis=-2).astype(jnp.int32))

    return apply_op(f_mask if return_mask else f, x,
                    name="adaptive_max_pool2d")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    ks = _pair(kernel_sizes)
    st = _pair(strides)
    pd = _pair(paddings)
    dl = _pair(dilations)

    def f(v):
        n, c, h, w = v.shape
        patches = jax.lax.conv_general_dilated_patches(
            v, filter_shape=ks, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl,
            dimension_numbers=jax.lax.conv_dimension_numbers(v.shape, (1, 1) + ks, ("NCHW", "OIHW", "NCHW")),
        )
        # [N, C*kh*kw, OH, OW] -> [N, C*kh*kw, L]
        return patches.reshape(n, patches.shape[1], -1)

    return apply_op(f, _t(x), name="unfold")


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
                data_format="NCHW"):
    x = _t(x)
    n, c, h, w = x._value.shape
    if size is None:
        sf = _pair(scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    else:
        size = _pair(size)
    method = {"nearest": "nearest", "bilinear": "bilinear", "bicubic": "cubic",
              "linear": "linear", "area": "nearest"}[mode]

    def f(v):
        return jax.image.resize(v, (v.shape[0], v.shape[1], size[0], size[1]), method=method)

    return apply_op(f, x, name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = int(upscale_factor)

    def f(v):
        n, c, h, w = v.shape
        v = v.reshape(n, c // (r * r), r, r, h, w)
        v = v.transpose(0, 1, 4, 2, 5, 3)
        return v.reshape(n, c // (r * r), h * r, w * r)

    return apply_op(f, _t(x), name="pixel_shuffle")


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    nd = len(tuple(normalized_shape))

    def f(v, *wb):
        axes = tuple(range(v.ndim - nd, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(v - mean), axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + epsilon)
        if wb:
            if len(wb) == 2:
                out = out * wb[0] + wb[1]
            elif weight is not None:
                out = out * wb[0]
            else:
                out = out + wb[0]
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *[_t(a) for a in args], name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, axis=-1):
    """RMSNorm (LLaMA-family). The composite form is the DEFAULT on purpose:
    XLA fuses it into the surrounding ops and measures ~3x faster than the
    standalone Pallas kernel (`paddle_tpu.ops.pallas.rmsnorm`, kept for
    isolated-norm workloads — see its docstring for the numbers)."""

    def f(v, *w):
        var = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=axis, keepdims=True)
        out = (v.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(v.dtype)
        if w:
            out = out * w[0]
        return out

    args = [x] if weight is None else [x, weight]
    return apply_op(f, *[_t(a) for a in args], name="rms_norm")


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None):
    x = _t(x)
    nd = x._value.ndim
    axes = tuple(i for i in range(nd) if i != 1)
    shape = [1] * nd
    shape[1] = x._value.shape[1]

    use_batch_stats = training and not use_global_stats
    if use_batch_stats:
        def f(v, *wb):
            mean = jnp.mean(v, axis=axes)
            var = jnp.var(v, axis=axes)
            out = (v - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(shape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(shape)
            return out, mean, var

        args = [x] + [_t(a) for a in (weight, bias) if a is not None]
        out, mean, var = apply_op(f, *args, name="batch_norm")
        # running-stat EMA goes through apply_op (not raw host math) so a
        # recording static Program captures it as an instruction; _set_value
        # with the result Tensor then registers a per-run writeback
        if running_mean is not None:
            def ema(old, new):
                return momentum * old + (1 - momentum) * new

            running_mean._set_value(
                apply_op(ema, _t(running_mean), mean.detach(), name="bn_stat_update"))
            running_var._set_value(
                apply_op(ema, _t(running_var), var.detach(), name="bn_stat_update"))
        return out

    def f(v, m, va, *wb):
        out = (v - m.reshape(shape)) * jax.lax.rsqrt(va.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x, _t(running_mean), _t(running_var)] + [_t(a) for a in (weight, bias) if a is not None]
    return apply_op(f, *args, name="batch_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW"):
    x = _t(x)
    nd = x._value.ndim
    axes = tuple(range(2, nd))
    shape = [1, x._value.shape[1]] + [1] * (nd - 2)

    def f(v, *wb):
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + eps)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x] + [_t(a) for a in (weight, bias) if a is not None]
    return apply_op(f, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW"):
    x = _t(x)

    def f(v, *wb):
        n, c = v.shape[0], v.shape[1]
        rest = v.shape[2:]
        g = v.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(v.shape)
        shape = [1, c] + [1] * len(rest)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x] + [_t(a) for a in (weight, bias) if a is not None]
    return apply_op(f, *args, name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
    def f(v):
        sq = jnp.square(v)
        half = size // 2
        pads = ((0, 0), (half, size - half - 1), (0, 0), (0, 0))
        s = jax.lax.reduce_window(sq, 0.0, jax.lax.add, (1, size, 1, 1), (1, 1, 1, 1), pads)
        return v / jnp.power(k + alpha * s / size, beta)

    return apply_op(f, _t(x), name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12):
    def f(v):
        n = jnp.power(jnp.sum(jnp.power(jnp.abs(v), p), axis=axis, keepdims=True), 1.0 / p)
        return v / jnp.maximum(n, epsilon)

    return apply_op(f, _t(x), name="normalize")


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        return _t(x)
    from paddle_tpu.distributed.fleet.rng import current_dropout_key

    key = current_dropout_key()

    def f(v, k):
        shape = v.shape
        if axis is not None:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = tuple(s if i in axes else 1 for i, s in enumerate(v.shape))
        keep = jax.random.bernoulli(k, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0)
        return jnp.where(keep, v, 0.0)

    # key as a positional arg (not a closure) so static-graph replay can
    # substitute a fresh fold per run (rng_args marks it for the recorder)
    return apply_op(f, _t(x), key, name="dropout", rng_args=(1,))


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    return dropout(x, p, axis=(0, 1), training=training)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return _t(x)
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    key = default_generator.next_key()

    def f(v, k):
        keep = jax.random.bernoulli(k, 1.0 - p, v.shape)
        a = (1.0 / math.sqrt((1 - p) * (1 + p * alpha_p ** 2))) if p < 1 else 0.0
        b = -a * alpha_p * p
        return a * jnp.where(keep, v, alpha_p) + b

    return apply_op(f, _t(x), key, name="alpha_dropout", rng_args=(1,))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _reduce(val, reduction):
    if reduction == "mean":
        return jnp.mean(val)
    if reduction == "sum":
        return jnp.sum(val)
    return val


def _fused_ce_reduce(nll, valid, reduction, out_shape, dtype):
    """Shared reduction over fp32 per-token fused-CE losses, matching the
    unfused path's semantics exactly (mean = over non-ignored tokens)."""
    if reduction == "mean":
        out = jnp.sum(nll) / jnp.maximum(jnp.sum(valid.astype(nll.dtype)), 1.0)
    elif reduction == "sum":
        out = jnp.sum(nll)
    else:
        out = nll.reshape(out_shape)
    return out.astype(dtype)


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
                  use_fused=None):
    """reference: python/paddle/nn/functional/loss.py cross_entropy.

    Fast path: hard-label softmax CE lowers to the chunked fused kernel
    (`paddle_tpu.ops.pallas.fused_ce`) — a custom-vjp that never materializes
    the [tokens, classes] log-softmax in forward or backward. `use_fused`
    overrides the `use_fused_cross_entropy` flag per call (the escape hatch).
    """
    input = _t(input)
    nd = input._value.ndim
    fused_ok = (use_fused if use_fused is not None
                else flag("use_fused_cross_entropy"))
    if (fused_ok and use_softmax and not soft_label and weight is None
            and nd >= 2 and axis in (-1, nd - 1)):
        def f(logits, lab):
            from paddle_tpu.ops.pallas.fused_ce import (
                softmax_cross_entropy_loss)

            lv = lab
            if lv.ndim == logits.ndim:
                lv = jnp.squeeze(lv, -1)
            flat = logits.reshape(-1, logits.shape[-1])
            labf = lv.reshape(-1)
            nll = softmax_cross_entropy_loss(
                flat, labf, ignore_index=ignore_index,
                label_smoothing=label_smoothing, mp_axis=None)
            return _fused_ce_reduce(nll, labf != ignore_index, reduction,
                                    lv.shape, logits.dtype)

        return apply_op(f, input, _t(label), name="cross_entropy")

    def f(logits, lab, *w):
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.clip(logits, 1e-15, 1.0))
        nclass = logits.shape[axis]
        if soft_label:
            soft = lab
            if label_smoothing > 0.0:
                soft = soft * (1 - label_smoothing) + label_smoothing / nclass
            nll = -jnp.sum(soft * logp, axis=axis)
        else:
            # gather the label log-prob instead of materializing a one-hot
            # ([N, vocab] would dominate memory at LM scale)
            li = lab
            if li.ndim == logp.ndim:  # [..., 1]
                li = jnp.squeeze(li, axis)
            safe = jnp.clip(li, 0, nclass - 1)
            picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis), axis)
            picked = jnp.squeeze(picked, axis)
            if label_smoothing > 0.0:
                nll = -(1 - label_smoothing) * picked - label_smoothing * jnp.mean(logp, axis=axis)
            else:
                nll = -picked
        if not soft_label:
            li = lab
            if li.ndim == logp.ndim:
                li = jnp.squeeze(li, axis)
            valid = li != ignore_index
            nll = jnp.where(valid, nll, 0.0)
            if w:
                cw = jnp.take(w[0], jnp.clip(li, 0, nclass - 1))
                nll = nll * cw
                if reduction == "mean":
                    denom = jnp.sum(jnp.where(valid, cw, 0.0))
                    return jnp.sum(nll) / jnp.maximum(denom, 1e-12)
            if reduction == "mean":
                return jnp.sum(nll) / jnp.maximum(jnp.sum(valid.astype(nll.dtype)), 1.0)
        return _reduce(nll, reduction)

    args = [_t(input), _t(label)]
    if weight is not None:
        args.append(_t(weight))
    return apply_op(f, *args, name="cross_entropy")


def parallel_cross_entropy(input, label, ignore_index=-100,
                           label_smoothing=0.0, use_fused=None):
    """Megatron-style vocab-parallel softmax CE (reference
    ParallelCrossEntropy, fleet/layers/mpu/mp_layers.py:742) on
    (possibly mp-sharded) logits. Returns the PER-TOKEN loss shaped like
    `label`, with ignored tokens contributing 0.

    Inside shard_map with the "mp" axis bound, `input` is the local vocab
    shard: the max / sum-exp / target-logit stats reduce over the axis with
    pmax/psum so no rank materializes a full vocab row. The hot path is the
    chunked fused kernel (custom vjp, fp32 stats); `use_fused=False` (or the
    `use_fused_cross_entropy` flag) falls back to the unfused formula."""
    input = _t(input)
    lab = _t(label)
    if lab._value.ndim == input._value.ndim:
        from paddle_tpu.ops.manipulation import squeeze

        lab = squeeze(lab, -1)
    fused_ok = (use_fused if use_fused is not None
                else flag("use_fused_cross_entropy"))
    if fused_ok:
        def f(logits, lv):
            from paddle_tpu.ops.pallas.fused_ce import (
                softmax_cross_entropy_loss)

            flat = logits.reshape(-1, logits.shape[-1])
            nll = softmax_cross_entropy_loss(
                flat, lv.reshape(-1), ignore_index=ignore_index,
                label_smoothing=label_smoothing, mp_axis="auto")
            return nll.reshape(lv.shape)

        return apply_op(f, input, lab, name="parallel_cross_entropy")

    def f(logits, lv):
        from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import (
            MP_AXIS, mp_axis_bound)

        bound = mp_axis_bound()
        lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        if bound:
            lmax = jax.lax.pmax(lmax, MP_AXIS)
        shifted = logits - lmax
        sumexp = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
        if bound:
            sumexp = jax.lax.psum(sumexp, MP_AXIS)
        logz = jnp.log(sumexp)
        if bound:
            n_local = logits.shape[-1]
            start = jax.lax.axis_index(MP_AXIS) * n_local
            local_lab = lv - start
            in_range = (local_lab >= 0) & (local_lab < n_local)
            safe = jnp.clip(local_lab, 0, n_local - 1)
            picked = jnp.take_along_axis(shifted, safe[..., None], axis=-1)
            picked = jnp.where(in_range[..., None], picked, 0.0)
            picked = jax.lax.psum(picked, MP_AXIS)
        else:
            picked = jnp.take_along_axis(shifted, lv[..., None], axis=-1)
        loss = (logz - picked)[..., 0]
        valid = lv != ignore_index
        return jnp.where(valid, loss, 0.0)

    return apply_op(f, input, lab, name="parallel_cross_entropy")


def fused_linear_cross_entropy(x, weight, label, bias=None, ignore_index=-100,
                               reduction="mean", label_smoothing=0.0,
                               z_loss=0.0, chunk_tokens=0, chunk_vocab=0,
                               variant="auto"):
    """loss = CE(x @ weight [+ bias], label) WITHOUT materializing the
    [tokens, vocab] logits in forward or backward (chunked custom vjp,
    `paddle_tpu.ops.pallas.fused_ce`; see docs/fused_head_cross_entropy.md).

    x: [..., hidden]; weight: [hidden, vocab] (the local shard under bound
    mp — stats then reduce over the "mp" axis, Megatron-style); label:
    integer [...] matching x's leading dims. `z_loss` adds the
    `z * logsumexp^2` stabilizer to both value and gradient. `reduction`:
    "mean" (over non-ignored tokens), "sum", "mean_all" (over every token,
    like `parallel_cross_entropy(...).mean()`) or "none" (per token, shaped
    like `label`); a reduced loss computes its gradients in the same pass
    as its value."""
    x = _t(x)
    lab = _t(label)
    if lab._value.ndim == x._value.ndim:
        from paddle_tpu.ops.manipulation import squeeze

        lab = squeeze(lab, -1)

    def f(xv, wv, lv, *bv):
        from paddle_tpu.ops.pallas.fused_ce import (
            fused_linear_cross_entropy_loss)

        flat = xv.reshape(-1, xv.shape[-1])
        labf = lv.reshape(-1)
        out = fused_linear_cross_entropy_loss(
            flat, wv, labf, bv[0] if bv else None,
            ignore_index=ignore_index, label_smoothing=label_smoothing,
            z_loss=z_loss, chunk_tokens=chunk_tokens, chunk_vocab=chunk_vocab,
            variant=variant, mp_axis="auto", reduction=reduction)
        return out.reshape(lv.shape) if reduction == "none" else out

    args = [x, _t(weight), lab] + ([_t(bias)] if bias is not None else [])
    return apply_op(f, *args, name="fused_linear_cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index,
                         reduction="none", axis=axis)
    loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    def f(p, y, *w):
        val = -(y * jnp.log(jnp.clip(p, 1e-12, 1.0)) + (1 - y) * jnp.log(jnp.clip(1 - p, 1e-12, 1.0)))
        if w:
            val = val * w[0]
        return _reduce(val, reduction)

    args = [_t(input), _t(label)] + ([_t(weight)] if weight is not None else [])
    return apply_op(f, *args, name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None):
    def f(z, y, *extra):
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]; i += 1
        log_sig = jax.nn.log_sigmoid(z)
        log_one_minus = jax.nn.log_sigmoid(-z)
        if pw is not None:
            val = -(pw * y * log_sig + (1 - y) * log_one_minus)
        else:
            val = -(y * log_sig + (1 - y) * log_one_minus)
        if w is not None:
            val = val * w
        return _reduce(val, reduction)

    args = [_t(logit), _t(label)]
    if weight is not None:
        args.append(_t(weight))
    if pos_weight is not None:
        args.append(_t(pos_weight))
    return apply_op(f, *args, name="bce_with_logits")


def mse_loss(input, label, reduction="mean"):
    return apply_op(
        lambda a, b: _reduce(jnp.square(a - b), reduction), _t(input), _t(label), name="mse_loss"
    )


def square_error_cost(input, label):
    return apply_op(lambda a, b: jnp.square(a - b), _t(input), _t(label), name="square_error_cost")


def l1_loss(input, label, reduction="mean"):
    return apply_op(
        lambda a, b: _reduce(jnp.abs(a - b), reduction), _t(input), _t(label), name="l1_loss"
    )


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    def f(logp, lab, *w):
        nclass = logp.shape[-1]
        oh = jax.nn.one_hot(lab, nclass, dtype=logp.dtype)
        nll = -jnp.sum(oh * logp, axis=-1)
        valid = lab != ignore_index
        nll = jnp.where(valid, nll, 0.0)
        if w:
            cw = jnp.take(w[0], jnp.clip(lab, 0, nclass - 1))
            nll = nll * cw
        if reduction == "mean":
            denom = jnp.sum(valid.astype(nll.dtype)) if not w else jnp.sum(jnp.where(valid, jnp.take(w[0], jnp.clip(lab, 0, nclass - 1)), 0.0))
            return jnp.sum(nll) / jnp.maximum(denom, 1e-12)
        return _reduce(nll, reduction)

    args = [_t(input), _t(label)] + ([_t(weight)] if weight is not None else [])
    return apply_op(f, *args, name="nll_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    def f(a, b):
        d = jnp.abs(a - b)
        val = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(val, reduction)

    return apply_op(f, _t(input), _t(label), name="smooth_l1_loss")


def kl_div(input, label, reduction="mean", log_target=False):
    def f(logp, q):
        if log_target:
            val = jnp.exp(q) * (q - logp)
        else:
            val = q * (jnp.log(jnp.clip(q, 1e-12, None)) - logp)
        if reduction == "batchmean":
            return jnp.sum(val) / logp.shape[0]
        return _reduce(val, reduction)

    return apply_op(f, _t(input), _t(label), name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    return apply_op(
        lambda a, b, y: _reduce(jnp.maximum(0.0, -y * (a - b) + margin), reduction),
        _t(input), _t(other), _t(label), name="margin_ranking_loss",
    )


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)

    return apply_op(f, _t(x1), _t(x2), name="cosine_similarity")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    return apply_op(
        lambda x, y: _reduce(jnp.where(y == 1, x, jnp.maximum(0.0, margin - x)), reduction),
        _t(input), _t(label), name="hinge_embedding_loss",
    )


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12
        )
        val = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(val, reduction)

    return apply_op(f, _t(input1), _t(input2), _t(label), name="cosine_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, eps=1e-6,
                        swap=False, reduction="mean"):
    def f(a, pos, neg):
        dp = jnp.power(jnp.sum(jnp.power(jnp.abs(a - pos) + eps, p), axis=-1), 1 / p)
        dn = jnp.power(jnp.sum(jnp.power(jnp.abs(a - neg) + eps, p), axis=-1), 1 / p)
        if swap:
            dsn = jnp.power(jnp.sum(jnp.power(jnp.abs(pos - neg) + eps, p), axis=-1), 1 / p)
            dn = jnp.minimum(dn, dsn)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)

    return apply_op(f, _t(input), _t(positive), _t(negative), name="triplet_margin_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum"):
    def f(z, y, *n):
        p = jax.nn.sigmoid(z)
        ce = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        val = a_t * jnp.power(1 - p_t, gamma) * ce
        if n:
            val = val / n[0]
        return _reduce(val, reduction)

    args = [_t(logit), _t(label)] + ([_t(normalizer)] if normalizer is not None else [])
    return apply_op(f, *args, name="sigmoid_focal_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (reference: warpctc-backed paddle.nn.functional.ctc_loss).

    TPU-native: optax's pure-jax forward-algorithm CTC — a lax.scan over
    time, fully differentiable and jit/shard-compatible (no warpctc
    binary). log_probs: [T, N, C] (paddle layout), labels: [N, S]."""
    import optax

    def f(lp, lab, in_len, lab_len):
        logits = jnp.transpose(lp, (1, 0, 2))  # [N, T, C]
        n, t, _ = logits.shape
        s = lab.shape[1]
        logit_pad = (jnp.arange(t)[None, :] >= in_len[:, None]).astype(jnp.float32)
        label_pad = (jnp.arange(s)[None, :] >= lab_len[:, None]).astype(jnp.float32)
        per_seq = optax.ctc_loss(logits, logit_pad, lab.astype(jnp.int32),
                                 label_pad, blank_id=blank)
        if norm_by_times:
            # reference warpctc semantics: scale only the GRADIENT by 1/T;
            # the reported loss value is unchanged. value = per_seq,
            # d(out)/d(logits) = d(per_seq)/d(logits) / T.
            t_inv = per_seq / jnp.maximum(in_len.astype(per_seq.dtype), 1)
            per_seq = t_inv + jax.lax.stop_gradient(per_seq - t_inv)
        if reduction == "mean":
            # paddle/torch 'mean': divide by label length, then batch-mean
            per_seq = per_seq / jnp.maximum(lab_len.astype(per_seq.dtype), 1)
        return _reduce(per_seq, reduction)

    return apply_op(f, _t(log_probs), _t(labels), _t(input_lengths),
                    _t(label_lengths), name="ctc_loss")


def label_smooth(label, prior_dist=None, epsilon=0.1):
    def f(y, *pd):
        n = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / n

    args = [_t(label)] + ([_t(prior_dist)] if prior_dist is not None else [])
    return apply_op(f, *args, name="label_smooth")


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    l = _t(lengths)
    m = int(maxlen) if maxlen is not None else int(jnp.max(l._value))
    d = to_jax_dtype(dtype)
    return apply_op(
        lambda v: (jnp.arange(m)[None, :] < v[:, None]).astype(d), l, name="sequence_mask"
    )


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

_NEG_BIAS = -1e30  # additive mask floor: composes (sums) without fp32
                   # overflow, unlike finfo.min whose sum is -inf -> NaN

_warned_pallas_blocks: set = set()


def _warn_pallas_blocks_once(reason: str, shape_sig=None):
    """One-time XLA-fallback warning, deduplicated per (reason, shape
    signature) — NOT per process: a second, DISTINCT fallback cause (a new
    reason, or the same reason triggered by a different q/k/v geometry)
    must still surface instead of being swallowed by the first one."""
    key = (reason, shape_sig)
    if key not in _warned_pallas_blocks:
        import warnings

        _warned_pallas_blocks.add(key)
        at = f" (shapes {shape_sig})" if shape_sig is not None else ""
        warnings.warn(
            f"Pallas flash attention disabled for this shape{at}, using the "
            f"XLA fallback: {reason}", stacklevel=3)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None,
                                 segment_ids=None):
    """reference: nn/functional/flash_attention.py:722 scaled_dot_product_attention.

    Layout: [batch, seq, heads, head_dim] (paddle flash-attention convention).
    Uses the Pallas flash-attention kernel on a TPU backend (or under
    force_interpret()) when enabled and applicable — a kernel failure there
    raises; on a CPU backend, with dropout or with an explicit mask it is
    the XLA path (fused by the compiler; memory O(S^2) only at trace).

    segment_ids ([batch, seq] int32, sequence packing): attention becomes
    block-diagonal per packed document — position i attends to j only when
    segment_ids[b, i] == segment_ids[b, j] (composed with the causal and
    explicit masks). The Pallas kernel additionally SKIPS whole K blocks no
    segment of the Q block touches; the XLA fallback applies the equivalent
    dense mask so both paths compute the same math.

    Masks COMPOSE: an explicit `attn_mask` together with `is_causal=True`
    (and/or `segment_ids`) applies all of them — boolean masks and the
    causal/segment constraints become additive -1e30 biases, float masks add
    through unchanged, so no combination overflows to -inf/NaN.
    """
    if flag("use_pallas_attention") and dropout_p == 0.0 and attn_mask is None:
        from paddle_tpu.ops.pallas._compat import on_tpu
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bshd, interpret_forced, pallas_blocks_ok)

        if on_tpu() or interpret_forced():
            ok, reason = pallas_blocks_ok(int(_t(query).shape[1]))
            if not ok:
                # a bad FLAGS_flash_block_q/k override must not fail inside
                # the kernel launch: warn once PER (cause, geometry), run
                # the XLA path below
                _warn_pallas_blocks_once(
                    reason, shape_sig=tuple(_t(query).shape))
            else:
                # no catch around the kernel: on a TPU backend a flash
                # kernel that fails to trace, lower or compile must raise,
                # not quietly become the O(S^2) XLA attention below
                q, k, v = _t(query), _t(key), _t(value)
                args = [q, k, v]
                if segment_ids is not None:
                    args.append(_t(segment_ids))

                def fa(a, b, c, *s):
                    return flash_attention_bshd(
                        a, b, c, causal=is_causal,
                        segment_ids=s[0] if s else None)

                return apply_op(fa, *args, name="flash_attention")

    def f(q, k, v, *extra):
        # [B,S,H,D] -> [B,H,S,D]; GQA (fewer kv heads) via grouped einsum —
        # the shared K/V heads are never materialized per query head
        it = iter(extra)
        m = next(it) if attn_mask is not None else None
        seg = next(it) if segment_ids is not None else None
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        b, hq, s_len, d = qh.shape
        hkv = kh.shape[1]
        if hkv == 0 or hq % hkv != 0:
            raise ValueError(
                f"q heads must be a multiple of kv heads, got {hq} and {hkv}")
        g = hq // hkv
        qg = qh.reshape(b, hkv, g, s_len, d)
        scores = jnp.einsum("bhgsd,bhtd->bhgst", qg, kh).astype(
            jnp.float32) / math.sqrt(q.shape[-1])
        t_len = scores.shape[-1]
        # masks COMPOSE in two tiers: HARD masks (bool attn_mask, causal,
        # segment) combine into one validity boolean; a SOFT (float)
        # attn_mask adds through, clamped to -1e30 so a finfo.min-style
        # user mask neither overflows to -inf/NaN nor outranks a hard mask
        # (hard-masked scores sit strictly below every soft-masked one).
        valid = None
        if m is not None:
            mask = jnp.broadcast_to(m, (b, hq, s_len, t_len))
            mask = mask.reshape(b, hkv, g, s_len, t_len)
            if mask.dtype == jnp.bool_:
                valid = mask
            else:
                scores = scores + jnp.maximum(
                    mask.astype(jnp.float32), _NEG_BIAS)
        if is_causal:
            causal = jnp.tril(jnp.ones((s_len, t_len), bool))
            valid = causal if valid is None else valid & causal
        if seg is not None:
            same = seg[:, None, None, :, None] == seg[:, None, None, None, :]
            valid = same if valid is None else valid & same
        if valid is not None:
            scores = jnp.where(valid, scores, 2.0 * _NEG_BIAS)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        # v's own width (latent attention: 128 beside a query/key width of 192)
        out = jnp.einsum("bhgst,bhtd->bhgsd", probs, vh).reshape(
            b, hq, s_len, vh.shape[-1])
        return jnp.swapaxes(out, 1, 2)

    args = [_t(query), _t(key), _t(value)]
    if attn_mask is not None:
        args.append(_t(attn_mask))
    if segment_ids is not None:
        args.append(_t(segment_ids))
    out = apply_op(f, *args, name="sdpa")
    if dropout_p > 0.0 and training:
        out = dropout(out, dropout_p, training=training)
    return out


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """reference: nn/functional/flash_attention.py:147."""
    out = scaled_dot_product_attention(
        query, key, value, dropout_p=dropout, is_causal=causal, training=training
    )
    if return_softmax:
        return out, None
    return out, None


# ---------------------------------------------------------------------------
# functional tail (reference ops.yaml: huber_loss, log_loss, channel_shuffle,
# pixel_unshuffle, temporal_shift, gumbel_softmax, swiglu, lp_pool2d,
# max_pool2d_with_index/unpool, affine_grid, grid_sample, fold)

def huber_loss(input, label, delta=1.0, reduction="mean"):
    def f(x, y):
        d = x - y
        ad = jnp.abs(d)
        return _reduce(jnp.where(ad <= delta, 0.5 * d * d,
                                 delta * (ad - 0.5 * delta)), reduction)

    return apply_op(f, _t(input), _t(label), name="huber_loss")


def log_loss(input, label, epsilon=1e-4):
    def f(p, y):
        return -y * jnp.log(p + epsilon) - (1.0 - y) * jnp.log(1.0 - p + epsilon)

    return apply_op(f, _t(input), _t(label), name="log_loss")


def channel_shuffle(x, groups, data_format="NCHW"):
    def f(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            return v.reshape(n, groups, c // groups, h, w) \
                    .transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
        n, h, w, c = v.shape
        return v.reshape(n, h, w, groups, c // groups) \
                .transpose(0, 1, 2, 4, 3).reshape(n, h, w, c)

    return apply_op(f, _t(x), name="channel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = int(downscale_factor)

    def f(v):
        n, c, h, w = v.shape
        v = v.reshape(n, c, h // r, r, w // r, r)
        return v.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * r * r, h // r, w // r)

    return apply_op(f, _t(x), name="pixel_unshuffle")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    def f(v):
        nt, c, h, w = v.shape
        n = nt // seg_num
        v = v.reshape(n, seg_num, c, h, w)
        fold_c = int(c * shift_ratio)
        back = jnp.concatenate([v[:, 1:, :fold_c],
                                jnp.zeros_like(v[:, :1, :fold_c])], axis=1)
        fwd = jnp.concatenate([jnp.zeros_like(v[:, :1, fold_c:2 * fold_c]),
                               v[:, :-1, fold_c:2 * fold_c]], axis=1)
        keep = v[:, :, 2 * fold_c:]
        return jnp.concatenate([back, fwd, keep], axis=2).reshape(nt, c, h, w)

    return apply_op(f, _t(x), name="temporal_shift")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    from paddle_tpu.ops.random_state import default_generator

    key = default_generator.next_key()

    def f(v, k):
        u = jax.random.uniform(k, v.shape, v.dtype, 1e-20, 1.0)
        g = -jnp.log(-jnp.log(u))
        y = jax.nn.softmax((v + g) / temperature, axis=axis)
        if hard:
            oh = jax.nn.one_hot(jnp.argmax(y, axis=axis), v.shape[axis],
                                axis=axis, dtype=v.dtype)
            return oh + y - jax.lax.stop_gradient(y)  # straight-through
        return y

    return apply_op(f, _t(x), key, name="gumbel_softmax", rng_args=(1,))


def swiglu(x, y=None):
    """reference ops.yaml swiglu: silu(x) * y, with y defaulting to the
    second half of x split on the last axis (fused-FFN gate)."""
    if y is not None:
        return apply_op(lambda a, b: jax.nn.silu(a) * b, _t(x), _t(y),
                        name="swiglu")

    def f(v):
        a, b = jnp.split(v, 2, axis=-1)
        return jax.nn.silu(a) * b

    return apply_op(f, _t(x), name="swiglu")


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW"):
    p = float(norm_type)
    ks = _pair(kernel_size, 2)
    st = _pair(stride if stride is not None else kernel_size, 2)
    pd = _pair(padding, 2)

    def f(v):
        s = jax.lax.reduce_window(
            jnp.abs(v) ** p, 0.0, jax.lax.add, (1, 1) + ks, (1, 1) + st,
            ((0, 0), (0, 0)) + tuple((q, q) for q in pd))
        return s ** (1.0 / p)

    return apply_op(f, _t(x), name="lp_pool2d")


def _max_pool_with_index_nd(x, kernel_size, stride, padding, nd,
                            ceil_mode=False):
    """N-d max pool returning (out, flat-spatial argmax indices) — the
    machinery behind max_pool2d_with_index and every return_mask=True pool
    (reference ops.yaml max_pool2d_with_index; feeds max_unpool*d).
    Indices are exact int32 arithmetic (window start + in-window offset),
    not a float gather — no 2^24 precision cliff on large volumes."""
    ks = _pair(kernel_size, nd)
    st = _pair(stride if stride is not None else kernel_size, nd)
    pd = _pair(padding, nd)

    def f(v):
        n, c = v.shape[0], v.shape[1]
        sp = v.shape[2:]
        if ceil_mode:
            osp_t = [-(-(sp[d] + 2 * pd[d] - ks[d]) // st[d]) + 1
                     for d in range(nd)]
            # torch/paddle: the last window must start inside input+left-pad
            osp_t = [o - 1 if (o - 1) * st[d] >= sp[d] + pd[d] else o
                     for d, o in enumerate(osp_t)]
        else:
            osp_t = [(sp[d] + 2 * pd[d] - ks[d]) // st[d] + 1
                     for d in range(nd)]
        # right-pad enough that every ceil-mode window exists; finite
        # dtype-min padding (NOT -inf: the patches extraction is a one-hot
        # conv and -inf * 0 = NaN) never wins an argmax — windows always
        # overlap valid input
        padw = ((0, 0), (0, 0)) + tuple(
            (pd[d], max((osp_t[d] - 1) * st[d] + ks[d] - sp[d] - pd[d], 0))
            for d in range(nd))
        vpad = jnp.pad(v, padw, constant_values=jnp.finfo(v.dtype).min)
        patches = jax.lax.conv_general_dilated_patches(
            vpad, ks, st, "VALID")  # (N, C*prod(ks), *osp) channel-major
        patches = patches[(slice(None), slice(None))
                          + tuple(slice(0, o) for o in osp_t)]
        osp = patches.shape[2:]
        kprod = int(np.prod(ks))
        pr = patches.reshape((n, c, kprod) + osp)
        am = jnp.argmax(pr, axis=2)
        out = jnp.take_along_axis(pr, am[:, :, None], axis=2)[:, :, 0]
        # decompose the in-window argmax (row-major over ks) and add the
        # window start to get exact global per-dim coords -> flat index
        rem = am.astype(jnp.int32)
        flat = jnp.zeros(am.shape, jnp.int32)
        for d in range(nd):
            k_rest = int(np.prod(ks[d + 1:], dtype=np.int64))
            off_d = rem // k_rest
            rem = rem % k_rest
            bshape = [1, 1] + [1] * nd
            bshape[2 + d] = osp[d]
            start_d = (jnp.arange(osp[d], dtype=jnp.int32) * st[d]
                       - pd[d]).reshape(bshape)
            flat = flat * sp[d] + (off_d + start_d)
        return out, flat

    return apply_op(f, _t(x), name=f"max_pool{nd}d_with_index")


def max_pool2d_with_index(x, kernel_size, stride=None, padding=0,
                          ceil_mode=False):
    """Max pool returning flat (h*w) argmax indices per output cell
    (reference ops.yaml max_pool2d_with_index; feeds max_unpool2d)."""
    return _max_pool_with_index_nd(x, kernel_size, stride, padding, 2)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format="NCHW"):
    ks = _pair(kernel_size, 2)
    st = _pair(stride if stride is not None else kernel_size, 2)

    def f(v, idx):
        n, c, oh, ow = v.shape
        if output_size is not None:
            hh, ww = int(output_size[-2]), int(output_size[-1])
        else:
            hh = (oh - 1) * st[0] + ks[0] - 2 * _pair(padding, 2)[0]
            ww = (ow - 1) * st[1] + ks[1] - 2 * _pair(padding, 2)[1]
        flat = jnp.zeros((n, c, hh * ww), v.dtype)
        out = flat.at[
            jnp.arange(n)[:, None, None],
            jnp.arange(c)[None, :, None],
            idx.reshape(n, c, -1),
        ].set(v.reshape(n, c, -1))
        return out.reshape(n, c, hh, ww)

    return apply_op(f, _t(x), _t(indices), name="max_unpool2d")


def affine_grid(theta, out_shape, align_corners=True):
    """reference ops.yaml affine_grid: sampling grid from 2x3 affine maps."""
    n, c, h, w = [int(s) for s in out_shape]

    def f(th):
        if align_corners:
            ys = jnp.linspace(-1.0, 1.0, h)
            xs = jnp.linspace(-1.0, 1.0, w)
        else:
            ys = (jnp.arange(h) * 2 + 1) / h - 1.0
            xs = (jnp.arange(w) * 2 + 1) / w - 1.0
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # (H, W, 3)
        # sampling coordinates must not go through the bf16 MXU default —
        # a 1e-3 coordinate error visibly blurs the resample
        return jnp.einsum("hwk,njk->nhwj", base.astype(th.dtype), th,
                          precision=jax.lax.Precision.HIGHEST)

    return apply_op(f, _t(theta), name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """reference ops.yaml grid_sample: NCHW bilinear/nearest sampling at
    normalized grid locations with zeros/border/reflection padding."""

    def f(v, g):
        n, c, h, w = v.shape
        gx, gy = g[..., 0], g[..., 1]

        def unnorm(coord, size):
            if align_corners:
                return (coord + 1.0) * 0.5 * (size - 1)
            return ((coord + 1.0) * size - 1.0) * 0.5

        ix = unnorm(gx, w)
        iy = unnorm(gy, h)

        def reflect(coord, size):
            if align_corners:
                span = 2.0 * (size - 1)
                coord = jnp.abs(jnp.mod(coord, span))
                return jnp.where(coord > size - 1, span - coord, coord)
            span = 2.0 * size
            coord = jnp.mod(coord + 0.5, span)
            coord = jnp.abs(coord)
            coord = jnp.where(coord > size, span - coord, coord) - 0.5
            return jnp.clip(coord, 0, size - 1)

        if padding_mode == "reflection":
            ix = reflect(ix, w)
            iy = reflect(iy, h)
        elif padding_mode == "border":
            ix = jnp.clip(ix, 0, w - 1)
            iy = jnp.clip(iy, 0, h - 1)

        def gather(yi, xi):
            yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
            xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
            got = v[jnp.arange(n)[:, None, None], :, yc, xc]  # (N, Hg, Wg, C)
            if padding_mode == "zeros":
                ok = ((yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1))
                got = got * ok[..., None].astype(got.dtype)
            return got

        if mode == "nearest":
            out = gather(jnp.round(iy), jnp.round(ix))
            return jnp.moveaxis(out, -1, 1)

        x0 = jnp.floor(ix)
        y0 = jnp.floor(iy)
        x1, y1 = x0 + 1, y0 + 1
        wx = ix - x0
        wy = iy - y0
        out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[..., None]
               + gather(y0, x1) * (wx * (1 - wy))[..., None]
               + gather(y1, x0) * ((1 - wx) * wy)[..., None]
               + gather(y1, x1) * (wx * wy)[..., None])
        return jnp.moveaxis(out, -1, 1)

    return apply_op(f, _t(x), _t(grid), name="grid_sample")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    """col2im (reference ops.yaml fold): scatter-add unfolded columns back
    into the spatial map — inverse of `unfold`."""
    oh, ow = _pair(output_sizes, 2)
    kh, kw = _pair(kernel_sizes, 2)
    sh, sw = _pair(strides, 2)
    ph, pw = _pair(paddings, 2)
    dh, dw = _pair(dilations, 2)
    lh = (oh + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    lw = (ow + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1

    def f(v):
        n = v.shape[0]
        c = v.shape[1] // (kh * kw)
        cols = v.reshape(n, c, kh, kw, lh, lw)
        out = jnp.zeros((n, c, oh + 2 * ph, ow + 2 * pw), v.dtype)
        for i in range(kh):
            for j in range(kw):
                out = out.at[:, :,
                             i * dh: i * dh + lh * sh: sh,
                             j * dw: j * dw + lw * sw: sw].add(cols[:, :, i, j])
        return out[:, :, ph: ph + oh, pw: pw + ow]

    return apply_op(f, _t(x), name="fold")


__all__ += [
    "huber_loss", "log_loss", "channel_shuffle", "pixel_unshuffle",
    "temporal_shift", "gumbel_softmax", "swiglu", "lp_pool2d",
    "max_pool2d_with_index", "max_unpool2d", "affine_grid", "grid_sample",
    "fold",
]


# ---------------------------------------------------------------------------
# loss tail (reference nn/functional/loss.py: gaussian_nll_loss,
# poisson_nll_loss, multi_label_soft_margin_loss, soft_margin_loss,
# triplet_margin_with_distance_loss)

def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean"):
    def f(mu, y, var):
        var = jnp.maximum(var, epsilon)
        val = 0.5 * (jnp.log(var) + (y - mu) ** 2 / var)
        if full:
            val = val + 0.5 * math.log(2 * math.pi)
        return _reduce(val, reduction)

    return apply_op(f, _t(input), _t(label), _t(variance),
                    name="gaussian_nll_loss")


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean"):
    def f(x, y):
        if log_input:
            val = jnp.exp(x) - y * x
        else:
            val = x - y * jnp.log(x + epsilon)
        if full:
            # stirling term for y > 1
            stir = y * jnp.log(y) - y + 0.5 * jnp.log(2 * math.pi * y)
            val = val + jnp.where(y > 1, stir, 0.0)
        return _reduce(val, reduction)

    return apply_op(f, _t(input), _t(label), name="poisson_nll_loss")


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean"):
    def f(x, y, *w):
        val = -(y * jax.nn.log_sigmoid(x) + (1 - y) * jax.nn.log_sigmoid(-x))
        if w:
            val = val * w[0]
        return _reduce(val.mean(axis=-1), reduction)

    args = [_t(input), _t(label)] + ([_t(weight)] if weight is not None else [])
    return apply_op(f, *args, name="multi_label_soft_margin_loss")


def soft_margin_loss(input, label, reduction="mean"):
    def f(x, y):
        return _reduce(jnp.log1p(jnp.exp(-y * x)), reduction)

    return apply_op(f, _t(input), _t(label), name="soft_margin_loss")


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean"):
    dist = distance_function or (
        lambda a, b: paddle_pairwise_distance(a, b))

    d_ap = dist(_t(input), _t(positive))
    d_an = dist(_t(input), _t(negative))
    if swap:
        d_pn = dist(_t(positive), _t(negative))
        d_an = apply_op(jnp.minimum, d_an, d_pn, name="triplet_swap")

    def f(ap, an):
        return _reduce(jnp.maximum(ap - an + margin, 0.0), reduction)

    return apply_op(f, d_ap, d_an, name="triplet_margin_with_distance_loss")


def paddle_pairwise_distance(x, y, p=2.0, epsilon=1e-6):
    return apply_op(
        lambda a, b: ((jnp.abs(a - b) + epsilon) ** p).sum(-1) ** (1.0 / p),
        _t(x), _t(y), name="pairwise_distance")


__all__ += [
    "gaussian_nll_loss", "poisson_nll_loss", "multi_label_soft_margin_loss",
    "soft_margin_loss", "triplet_margin_with_distance_loss",
]


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean"):
    """RNN-Transducer loss (reference: warp-transducer-backed
    nn/functional/loss.py rnnt_loss:1983).

    TPU-native: the transducer forward algorithm as a lax.scan over frames
    with an inner scan over label positions — pure jax, differentiable,
    jit/shard-compatible (no warprnnt binary). input: [B, T, U+1, D]
    log-probs, label: [B, U]. fastemit_lambda applies FastEmit's (1+lambda)
    label-emission weighting inside the DP (the gradient-scaling form of
    warp-transducer, folded into the objective)."""
    import math as _math

    NEG = -1e30

    def f(lp, y, t_len, u_len):
        b, t_max, u_max1, _ = lp.shape
        u_max = u_max1 - 1
        blank_lp = lp[..., blank]                          # [B, T, U+1]
        lab_lp = jnp.take_along_axis(
            lp[:, :, :u_max, :], y[:, None, :, None].astype(jnp.int32),
            axis=-1)[..., 0]                              # [B, T, U]
        if fastemit_lambda:
            lab_lp = lab_lp + _math.log1p(fastemit_lambda)

        u_idx = jnp.arange(u_max1)
        u_valid = u_idx[None, :] <= u_len[:, None]        # [B, U+1]

        def u_step(carry, inp):
            # carry: alpha row being built (prefix over u); inp: (A_u, l_{u-1})
            prev, = carry
            a_u, l_prev = inp
            cur = jnp.logaddexp(a_u, prev + l_prev)
            return (cur,), cur

        def t_step(alpha_prev, t):
            # alpha_prev: [B, U+1] for frame t-1 -> alpha for frame t
            A = alpha_prev + blank_lp[:, t - 1, :]        # horizontal (blank) moves
            lab_t = lab_lp[:, t, :]                       # vertical moves in frame t

            def row(a_b, lab_b):
                first = a_b[0]
                (_, ), rest = jax.lax.scan(
                    u_step, (first,), (a_b[1:], lab_b))
                return jnp.concatenate([first[None], rest])

            alpha = jax.vmap(row)(A, lab_t)
            return jnp.where(u_valid, alpha, NEG), None

        # frame 0: only vertical moves from alpha[0,0]=0
        def row0(lab_b):
            init = jnp.zeros(())
            (_, ), rest = jax.lax.scan(
                u_step, (init,), (jnp.full((u_max,), NEG), lab_b))
            return jnp.concatenate([init[None], rest])

        alpha0 = jnp.where(u_valid, jax.vmap(row0)(lab_lp[:, 0, :]), NEG)

        def fori_body(t, alpha_all):
            alpha, final = alpha_all
            new_alpha, _ = t_step(alpha, t)
            active = (t < t_len)[:, None]
            alpha = jnp.where(active, new_alpha, alpha)
            # when t == t_len-1 this frame is the last: record terminal value
            at_end = (t == t_len - 1)
            term = jnp.take_along_axis(
                alpha + blank_lp[:, jnp.minimum(t, t_max - 1), :],
                u_len[:, None].astype(jnp.int32), axis=1)[:, 0]
            final = jnp.where(at_end, term, final)
            return (alpha, final)

        final0 = jnp.take_along_axis(
            alpha0 + blank_lp[:, 0, :], u_len[:, None].astype(jnp.int32),
            axis=1)[:, 0]
        final0 = jnp.where(t_len == 1, final0, NEG)
        alpha, final = jax.lax.fori_loop(1, t_max, fori_body, (alpha0, final0))
        per_seq = -final
        if reduction == "mean":
            per_seq = per_seq / jnp.maximum(u_len.astype(per_seq.dtype), 1)
        return _reduce(per_seq, reduction)

    return apply_op(f, _t(input), _t(label), _t(input_lengths),
                    _t(label_lengths), name="rnnt_loss")


__all__ += ["rnnt_loss"]


# ---------------------------------------------------------------------------
# functional tail 2: 3-D pools, pads, metric-learning losses, edit distance

def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW"):
    if return_mask:
        if data_format != "NCDHW":
            raise ValueError(
                "return_mask=True requires data_format='NCDHW' (reference "
                "paddle.nn.functional.max_pool3d contract)")
        return _max_pool_with_index_nd(x, kernel_size, stride, padding, 3,
                                       ceil_mode=ceil_mode)
    return _pool(x, kernel_size, stride, padding, 3, "max", -np.inf,
                 data_format, ceil_mode=ceil_mode)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW"):
    return _pool(x, kernel_size, stride, padding, 3, "avg", 0.0, data_format,
                 count_include_pad=not exclusive or padding == 0,
                 ceil_mode=ceil_mode)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    os3 = ((output_size,) * 3 if isinstance(output_size, int)
           else tuple(output_size))
    x = _t(x)
    if data_format == "NCDHW":
        d, h, w = x._value.shape[2:5]
    else:  # NDHWC
        d, h, w = x._value.shape[1:4]
    if (data_format == "NCDHW" and d % os3[0] == 0 and h % os3[1] == 0
            and w % os3[2] == 0):
        k = (d // os3[0], h // os3[1], w // os3[2])
        return _pool(x, k, k, 0, 3, "avg", 0.0, data_format)
    mats = [_adaptive_bin_matrix(s, o) for s, o in zip((d, h, w), os3)]

    def f(v):
        if data_format == "NCDHW":
            return jnp.einsum("ncdhw,od,ph,qw->ncopq", v, *mats,
                              preferred_element_type=v.dtype)
        return jnp.einsum("ndhwc,od,ph,qw->nopqc", v, *mats,
                          preferred_element_type=v.dtype)

    return apply_op(f, x, name="adaptive_avg_pool3d")


def zeropad2d(x, padding, data_format="NCHW"):
    p = padding if not isinstance(padding, int) else [padding] * 4

    def f(v):
        return jnp.pad(v, ((0, 0), (0, 0), (p[2], p[3]), (p[0], p[1])))

    return apply_op(f, _t(x), name="zeropad2d")


def pad3d(x, paddings, mode="constant", value=0.0, data_format="NCDHW"):
    p = paddings if not isinstance(paddings, int) else [paddings] * 6

    def f(v):
        pads = ((0, 0), (0, 0), (p[4], p[5]), (p[2], p[3]), (p[0], p[1]))
        if mode == "constant":
            return jnp.pad(v, pads, constant_values=value)
        m = {"reflect": "reflect", "replicate": "edge",
             "circular": "wrap"}[mode]
        return jnp.pad(v, pads, mode=m)

    return apply_op(f, _t(x), name="pad3d")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """reference loss.py npair_loss: cross-entropy over anchor-positive
    similarities + L2 on the embeddings."""

    def f(a, p, y):
        sim = a @ p.T  # [B, B]
        same = (y[:, None] == y[None, :]).astype(sim.dtype)
        tgt = same / same.sum(-1, keepdims=True)
        xent = (-tgt * jax.nn.log_softmax(sim, axis=-1)).sum(-1).mean()
        reg = l2_reg * (jnp.sum(a * a) + jnp.sum(p * p)) / a.shape[0] * 0.25
        return xent + reg

    return apply_op(f, _t(anchor), _t(positive), _t(labels), name="npair_loss")


def dice_loss(input, label, epsilon=1e-5):
    """reference loss.py dice_loss: 1 - 2|X∩Y| / (|X|+|Y|) over the
    one-hot label (input: [..., C] probabilities, label: [..., 1] ids)."""

    def f(x, y):
        oh = jax.nn.one_hot(y[..., 0].astype(jnp.int32), x.shape[-1],
                            dtype=x.dtype)
        reduce_dims = tuple(range(1, x.ndim))
        inter = jnp.sum(x * oh, axis=reduce_dims)
        union = jnp.sum(x, axis=reduce_dims) + jnp.sum(oh, axis=reduce_dims)
        return jnp.mean(1.0 - 2.0 * inter / (union + epsilon))

    return apply_op(f, _t(input), _t(label), name="dice_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5, margin3=0.0,
                         scale=64.0, group=None, return_softmax=False,
                         reduction="mean"):
    """ArcFace-family margin softmax (reference loss.py margin_cross_entropy:
    cos(m1*theta + m2) - m3 on the target logit, then scaled CE)."""

    def f(lg, y):
        yi = y.astype(jnp.int32).reshape(-1)
        oh = jax.nn.one_hot(yi, lg.shape[-1], dtype=lg.dtype)
        cos = jnp.clip(lg, -1.0, 1.0)
        theta = jnp.arccos(cos)
        target = jnp.cos(margin1 * theta + margin2) - margin3
        adj = jnp.where(oh > 0, target, cos) * scale
        lsm = jax.nn.log_softmax(adj, axis=-1)
        loss = -(oh * lsm).sum(-1)
        if reduction == "none":
            out_loss = loss
        elif reduction == "sum":
            out_loss = loss.sum()
        else:
            out_loss = loss.mean()
        if return_softmax:
            return out_loss, jnp.exp(lsm)
        return out_loss

    return apply_op(f, _t(logits), _t(label), name="margin_cross_entropy")


def embedding_bag(input, weight, mode="mean", padding_idx=None, name=None):
    """Sum/mean/max over each row's embedded ids (reference embedding_bag)."""

    def f(ids, w):
        emb = jnp.take(w, ids.astype(jnp.int32), axis=0)  # [B, L, D]
        if padding_idx is not None:
            mask = (ids != padding_idx)[..., None].astype(emb.dtype)
            emb = emb * mask
            denom = jnp.maximum(mask.sum(axis=-2), 1.0)
        else:
            denom = jnp.asarray(ids.shape[-1], emb.dtype)
        if mode == "sum":
            return emb.sum(axis=-2)
        if mode == "max":
            return emb.max(axis=-2)
        return emb.sum(axis=-2) / denom

    return apply_op(f, _t(input), _t(weight), name="embedding_bag")


def edit_distance(input, label, normalized=True, ignored_tokens=None):
    """Levenshtein distance per sequence pair (reference edit_distance op;
    host DP — dynamic-length string metric, not a device op)."""
    a_np = np.asarray(_t(input)._value)
    b_np = np.asarray(_t(label)._value)

    def lev(a, b):
        if ignored_tokens:
            a = [x for x in a if x not in ignored_tokens]
            b = [x for x in b if x not in ignored_tokens]
        m, n = len(a), len(b)
        dp = list(range(n + 1))
        for i in range(1, m + 1):
            prev = dp[0]
            dp[0] = i
            for j in range(1, n + 1):
                cur = dp[j]
                dp[j] = min(dp[j] + 1, dp[j - 1] + 1,
                            prev + (a[i - 1] != b[j - 1]))
                prev = cur
        return dp[n], n

    out, counts = [], []
    for a, b in zip(np.atleast_2d(a_np), np.atleast_2d(b_np)):
        d, n = lev(list(a), list(b))
        out.append(d / max(n, 1) if normalized else d)
        counts.append(1)
    return (Tensor(jnp.asarray(np.asarray(out, np.float32)[:, None])),
            Tensor(jnp.asarray(np.asarray(counts, np.int64))))


__all__ += [
    "max_pool3d", "avg_pool3d", "adaptive_avg_pool3d", "zeropad2d", "pad3d",
    "npair_loss", "dice_loss", "margin_cross_entropy", "embedding_bag",
    "edit_distance",
]


def _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                       dilation, groups, nd, name):
    """Shared N-d transpose conv: lhs-dilated conv with flipped IO kernel
    (the XLA-native formulation — no col2im scatter)."""
    strides = _pair(stride, nd)
    pads = _pair(padding, nd)
    dils = _pair(dilation, nd)
    opad = _pair(output_padding, nd)
    spatial = "DHW"[3 - nd:]
    io = ("NC" + spatial, "IO" + spatial, "NC" + spatial)
    xv = x._value if isinstance(x, Tensor) else x
    wv_shape = (weight._value.shape if isinstance(weight, Tensor)
                else weight.shape)
    grouped_shape = ((wv_shape[0] // groups, wv_shape[1] * groups)
                     + tuple(wv_shape[2:]))
    dn = jax.lax.conv_dimension_numbers(xv.shape, grouped_shape, io)
    pad_cfg = [
        (dils[i] * (wv_shape[2 + i] - 1) - pads[i],
         dils[i] * (wv_shape[2 + i] - 1) - pads[i] + opad[i])
        for i in range(nd)
    ]
    spatial_axes = tuple(range(2, 2 + nd))

    def f(v, w, *maybe_b):
        out = jax.lax.conv_general_dilated(
            v, w, window_strides=(1,) * nd, padding=pad_cfg,
            lhs_dilation=strides, rhs_dilation=dils, dimension_numbers=dn,
            feature_group_count=groups)
        if maybe_b:
            out = out + maybe_b[0].reshape((1, -1) + (1,) * nd)
        return out

    w = _t(weight)
    flip_w = apply_op(
        lambda u: _group_transpose_kernel(
            jnp.flip(u, axis=spatial_axes), groups, nd),
        w, name="flip")
    args = (_t(x), flip_w) if bias is None else (_t(x), flip_w, _t(bias))
    return apply_op(f, *args, name=name)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, output_size=None,
                     data_format="NCL"):
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 1,
                              "conv1d_transpose")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, output_size=None,
                     data_format="NCDHW"):
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 3,
                              "conv3d_transpose")


__all__ += ["conv1d_transpose", "conv3d_transpose"]
