"""Neural-network operators.

(reference: python/paddle/nn/functional/*; phi kernels conv_kernel,
pool_kernel, layer_norm_kernel, rms_norm_kernel (gpu/rms_norm_kernel.cu),
flash_attn_kernel (gpu/flash_attn_kernel.cu), softmax_with_cross_entropy.)

All kernels lower to XLA ops that map onto the MXU (conv/matmul via
lax.conv_general_dilated / dot_general) or fuse on the VPU. Hot fused ops
(flash attention, rms_norm, rope) have Pallas TPU implementations in
paddle_tpu/ops/pallas/ selected via FLAGS_use_pallas_kernels on TPU.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.dispatch import def_op
from ..core.dtype import convert_dtype

# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


@def_op("relu")
def relu(x):
    return jax.nn.relu(x)


@def_op("relu6")
def relu6(x):
    return jax.nn.relu6(x)


@def_op("leaky_relu")
def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


@def_op("elu")
def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


@def_op("selu")
def selu(x):
    return jax.nn.selu(x)


@def_op("celu")
def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


@def_op("gelu")
def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


@def_op("silu")
def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return silu(x)


@def_op("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@def_op("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@def_op("hardsigmoid")
def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return jnp.clip(x * slope + offset, 0.0, 1.0)


@def_op("hardswish")
def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


@def_op("hardtanh")
def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


@def_op("softplus")
def softplus(x, beta=1.0, threshold=20.0):
    return jnp.where(x * beta > threshold, x, jax.nn.softplus(x * beta) / beta)


@def_op("softsign")
def softsign(x):
    return jax.nn.soft_sign(x)


@def_op("tanhshrink")
def tanhshrink(x):
    return x - jnp.tanh(x)


@def_op("hardshrink")
def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@def_op("softshrink")
def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@def_op("prelu")
def prelu(x, weight):
    w = weight
    if w.size > 1 and x.ndim == 4:  # per-channel, NCHW
        w = w.reshape(1, -1, 1, 1)
    return jnp.where(x > 0, x, w * x)


@def_op("glu")
def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


@def_op("softmax")
def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@def_op("log_softmax")
def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@def_op("gumbel_softmax", differentiable=False)
def _gumbel_softmax(x, key, temperature=1.0, hard=False, axis=-1):
    g = jax.random.gumbel(key, x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False)
        y = y_hard + y - lax.stop_gradient(y)
    return y


# ---------------------------------------------------------------------------
# Linear / matmul fused
# ---------------------------------------------------------------------------


@def_op("linear")
def linear(x, weight, bias=None):
    """x @ W (+ b); paddle weight layout [in_features, out_features]."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


@def_op("fused_gemm_epilogue")
def fused_gemm_epilogue(x, weight, bias, trans_x=False, trans_y=False,
                        activation="none"):
    """matmul+bias+act fused (reference: fused_gemm_epilogue via cuBLASLt;
    on TPU XLA fuses the epilogue into the MXU matmul automatically)."""
    if trans_x:
        x = jnp.swapaxes(x, -1, -2)
    if trans_y:
        weight = jnp.swapaxes(weight, -1, -2)
    out = jnp.matmul(x, weight) + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    return out


# ---------------------------------------------------------------------------
# Convolutions / pooling
# ---------------------------------------------------------------------------


def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@def_op("conv2d")
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    stride = _norm_tuple(stride, 2)
    dilation = _norm_tuple(dilation, 2)
    if isinstance(padding, str):
        pad = padding.upper()
    elif isinstance(padding, int):
        pad = [(padding, padding), (padding, padding)]
    elif len(padding) == 2 and all(isinstance(p, int) for p in padding):
        pad = [(padding[0], padding[0]), (padding[1], padding[1])]
    else:
        pad = [tuple(p) for p in padding]
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=dn,
    )
    if bias is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


@def_op("conv1d")
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    stride = _norm_tuple(stride, 1)
    dilation = _norm_tuple(dilation, 1)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = padding if isinstance(padding, int) else padding[0]
        pad = [(p, p)]
    dn = ("NCH", "OIH", "NCH") if data_format == "NCL" else ("NHC", "HIO", "NHC")
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, feature_group_count=groups, dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape((1, -1, 1) if data_format == "NCL" else (1, 1, -1))
    return out


@def_op("conv2d_transpose")
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCHW"):
    stride = _norm_tuple(stride, 2)
    dilation = _norm_tuple(dilation, 2)
    p = _norm_tuple(padding, 2)
    opad = _norm_tuple(output_padding, 2)
    # paddle/conv-transpose semantics: insert (stride-1) zeros, flip kernel.
    kh = (weight.shape[2] - 1) * dilation[0] + 1
    kw = (weight.shape[3] - 1) * dilation[1] + 1
    pad = [(kh - 1 - p[0], kh - 1 - p[0] + opad[0]),
           (kw - 1 - p[1], kw - 1 - p[1] + opad[1])]
    w = jnp.flip(weight, axis=(2, 3))
    w = jnp.swapaxes(w, 0, 1)  # IOHW -> OIHW
    if groups > 1:
        ci = weight.shape[0]
        w = weight.reshape(groups, ci // groups, *weight.shape[1:])
        w = jnp.flip(w, axis=(3, 4))
        w = jnp.swapaxes(w, 1, 2).reshape(-1, ci // groups, *weight.shape[2:])
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad,
        lhs_dilation=stride, rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


@def_op("max_pool2d")
def max_pool2d(x, kernel_size=2, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW"):
    k = _norm_tuple(kernel_size, 2)
    s = _norm_tuple(stride if stride is not None else kernel_size, 2)
    p = _norm_tuple(padding, 2)
    if data_format == "NCHW":
        window = (1, 1) + k
        strides = (1, 1) + s
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1,) + k + (1,)
        strides = (1,) + s + (1,)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return lax.reduce_window(x, init, lax.max, window, strides, pads)


@def_op("avg_pool2d")
def avg_pool2d(x, kernel_size=2, stride=None, padding=0, ceil_mode=False,
               exclusive=True, data_format="NCHW"):
    k = _norm_tuple(kernel_size, 2)
    s = _norm_tuple(stride if stride is not None else kernel_size, 2)
    p = _norm_tuple(padding, 2)
    if data_format == "NCHW":
        window = (1, 1) + k
        strides = (1, 1) + s
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1,) + k + (1,)
        strides = (1,) + s + (1,)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    if exclusive and (p[0] or p[1]):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return summed / counts
    return summed / float(np.prod(k))


@def_op("adaptive_avg_pool2d")
def adaptive_avg_pool2d(x, output_size=1, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    if data_format == "NCHW":
        h_axis, w_axis = 2, 3
    else:
        h_axis, w_axis = 1, 2
    H, W = x.shape[h_axis], x.shape[w_axis]
    if H % out[0] == 0 and W % out[1] == 0:
        kh, kw = H // out[0], W // out[1]
        window = [1, 1, 1, 1]
        window[h_axis], window[w_axis] = kh, kw
        summed = lax.reduce_window(x, 0.0, lax.add, tuple(window), tuple(window),
                                   [(0, 0)] * 4)
        return summed / float(kh * kw)
    # general case: mean over index buckets
    return jax.image.resize(x, tuple(
        out[ (0 if i == h_axis else 1) ] if i in (h_axis, w_axis) else d
        for i, d in enumerate(x.shape)), method="linear")


@def_op("adaptive_max_pool2d")
def adaptive_max_pool2d(x, output_size=1, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    H, W = (x.shape[2], x.shape[3]) if data_format == "NCHW" else (x.shape[1], x.shape[2])
    kh, kw = H // out[0], W // out[1]
    window = (1, 1, kh, kw) if data_format == "NCHW" else (1, kh, kw, 1)
    return lax.reduce_window(x, -jnp.inf, lax.max, window, window, [(0, 0)] * 4)


@def_op("interpolate")
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    if data_format == "NCHW":
        H, W = x.shape[2], x.shape[3]
    else:
        H, W = x.shape[1], x.shape[2]
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (tuple, list)) else (
            scale_factor, scale_factor)
        size = (int(H * sf[0]), int(W * sf[1]))
    size = tuple(int(s) for s in size)
    if data_format == "NCHW":
        new_shape = x.shape[:2] + size
    else:
        new_shape = (x.shape[0],) + size + (x.shape[-1],)
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic",
              "linear": "linear", "area": "linear"}[mode]
    return jax.image.resize(x, new_shape, method=method)


@def_op("unfold")
def unfold(x, kernel_sizes=3, strides=1, paddings=0, dilations=1):
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    p = _norm_tuple(paddings, 2)
    d = _norm_tuple(dilations, 2)
    N, C = x.shape[0], x.shape[1]
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches.reshape(N, C * k[0] * k[1], -1)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


@def_op("layer_norm")
def layer_norm(x, weight=None, bias=None, epsilon=1e-5, begin_norm_axis=-1):
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@def_op("rms_norm")
def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1):
    """(reference: phi/kernels/gpu/rms_norm_kernel.cu; SPMD rule
    infermeta/spmd_rules/rms_norm.cc). Accumulates in fp32 like the ref;
    on TPU the fused Pallas kernel handles the common last-axis case."""
    if (weight is not None and bias is None
            and begin_norm_axis in (-1, x.ndim - 1)
            and weight.ndim == 1):
        from ..core import flags as _flags
        from .pallas import is_tpu_platform
        from .pallas.rms_norm import rms_norm_fused, rms_norm_supported

        if (_flags._get("use_pallas_kernels", True) and is_tpu_platform()
                and rms_norm_supported(x.shape)):
            return rms_norm_fused(x, weight, float(epsilon))
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
    var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    out = xf * lax.rsqrt(var + epsilon)
    out = out.astype(dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@def_op("batch_norm")
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW"):
    """Returns (out, new_running_mean, new_running_var)."""
    if x.ndim == 2:
        axes, shape = (0,), (1, -1)
    elif data_format == "NCHW":
        axes, shape = (0, 2, 3) if x.ndim == 4 else (0, 2), (1, -1) + (1,) * (x.ndim - 2)
    else:
        axes, shape = tuple(range(x.ndim - 1)), (1,) * (x.ndim - 1) + (-1,)
    if training:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        n = x.size // mean.size
        unbiased = var * n / max(n - 1, 1)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * unbiased
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    out = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, new_rm, new_rv


@def_op("group_norm")
def group_norm(x, weight=None, bias=None, epsilon=1e-5, groups=1,
               data_format="NCHW"):
    N, C = x.shape[0], x.shape[1]
    xg = x.reshape(N, groups, C // groups, *x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = (1, C) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@def_op("instance_norm")
def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@def_op("fused_layer_norm_residual")
def fused_layer_norm_residual(x, residual, weight=None, bias=None,
                              epsilon=1e-5):
    """add-residual + layernorm fused (reference:
    phi/kernels/fusion/gpu/fused_layernorm_kernel.cu); XLA fuses these on
    TPU so the "kernel" is just the composite, kept as one op for parity."""
    y = x + residual
    mean = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=-1, keepdims=True)
    out = (y - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out, y


# ---------------------------------------------------------------------------
# Dropout / embedding
# ---------------------------------------------------------------------------


@def_op("dropout")
def dropout(x, key, p=0.5, training=True, mode="upscale_in_train"):
    if not training or p == 0.0:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


@def_op("embedding")
def embedding(ids, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@def_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1):
    """Returns per-example loss (no reduction), paddle semantics."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        safe = jnp.where(lbl == ignore_index, 0, lbl)
        picked = jnp.take_along_axis(logp, safe[..., None].astype(jnp.int32),
                                     axis=axis)
        loss = -jnp.where((lbl == ignore_index)[..., None], 0.0, picked)
    return loss


@def_op("cross_entropy_loss")
def cross_entropy_loss(logits, label, weight=None, soft_label=False,
                       ignore_index=-100, reduction="mean", axis=-1,
                       label_smoothing=0.0):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    n_class = logits.shape[axis]
    if soft_label:
        target = label
        loss = -jnp.sum(target * logp, axis=axis)
        valid = jnp.ones(loss.shape, jnp.float32)
    else:
        lbl = label
        if lbl.ndim == logits.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        valid = (lbl != ignore_index).astype(jnp.float32)
        safe = jnp.where(lbl == ignore_index, 0, lbl).astype(jnp.int32)
        if label_smoothing > 0.0:
            onehot = jax.nn.one_hot(safe, n_class, dtype=logp.dtype, axis=axis)
            target = onehot * (1 - label_smoothing) + label_smoothing / n_class
            loss = -jnp.sum(target * logp, axis=axis) * valid
        else:
            picked = jnp.take_along_axis(logp, safe[..., None], axis=axis)
            loss = -jnp.squeeze(picked, axis=axis) * valid
        if weight is not None:
            w = jnp.take(weight, safe, axis=0) * valid
            loss = loss * jnp.take(weight, safe, axis=0)
            valid = w
    if reduction == "mean":
        denom = jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.sum(loss) / denom
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@def_op("mse_loss")
def mse_loss(input, label, reduction="mean"):
    return _reduce_loss(jnp.square(input - label), reduction)


@def_op("l1_loss")
def l1_loss(input, label, reduction="mean"):
    return _reduce_loss(jnp.abs(input - label), reduction)


@def_op("smooth_l1_loss")
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    return _reduce_loss(loss, reduction)


@def_op("nll_loss")
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    valid = (label != ignore_index)
    safe = jnp.where(valid, label, 0).astype(jnp.int32)
    picked = -jnp.take_along_axis(input, safe[..., None], axis=-1)[..., 0]
    w = jnp.ones_like(picked) if weight is None else jnp.take(weight, safe, axis=0)
    w = w * valid.astype(picked.dtype)
    loss = picked * w
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
    return _reduce_loss(loss, reduction)


@def_op("binary_cross_entropy")
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.clip(input, eps, None))
             + (1 - label) * jnp.log(jnp.clip(1 - input, eps, None)))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


@def_op("binary_cross_entropy_with_logits")
def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        loss = (1 - label) * logit + log_w * (
            jnp.log(1 + jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        loss = (1 - label) * logit + max_val + jnp.log(
            jnp.exp(-max_val) + jnp.exp(-logit - max_val))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


@def_op("kl_div")
def kl_div(input, label, reduction="mean"):
    loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    return _reduce_loss(loss, reduction)


@def_op("cosine_similarity")
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@def_op("scaled_dot_product_attention")
def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, scale=None,
                                 dropout_key=None):
    """Layout [batch, seqlen, num_heads, head_dim] (paddle flash_attention
    layout, nn/functional/flash_attention.py:147). XLA fallback path; the
    Pallas flash kernel registers over this on TPU."""
    B, S, H, D = q.shape
    scale = scale or (1.0 / np.sqrt(D))
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # B,H,S,D
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if is_causal:
        Sk = kf.shape[2]
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        scores = jnp.where(mask, scores, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, -jnp.inf)
        else:
            scores = scores + attn_mask
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p:
        if dropout_key is None:
            raise ValueError(
                "attention dropout requires an explicit dropout_key; call "
                "through nn.functional.scaled_dot_product_attention / "
                "flash_attention, which thread one from the global RNG")
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def rotate_half(x):
    """[-x2, x1] pairing used by neox-style rotary embeddings."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


@def_op("fused_rope")
def fused_rope(q, k, cos, sin, position_ids=None):
    """Rotary embedding applied to q,k [B,S,H,D] (reference:
    phi/kernels/fusion/gpu/fused_rope_kernel.cu; spmd_rules/fused_rope.cc).
    cos/sin: [S, D] or [1, S, 1, D]."""
    rot = rotate_half

    c = cos.reshape(1, cos.shape[-2], 1, cos.shape[-1]) if cos.ndim == 2 else cos
    s = sin.reshape(1, sin.shape[-2], 1, sin.shape[-1]) if sin.ndim == 2 else sin
    if position_ids is not None:
        c = jnp.take(c[0, :, 0], position_ids, axis=0)[:, :, None, :]
        s = jnp.take(s[0, :, 0], position_ids, axis=0)[:, :, None, :]
    q_out = q * c + rot(q) * s
    k_out = k * c + rot(k) * s
    return q_out, k_out


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


@def_op("label_smooth")
def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is None:
        return (1 - epsilon) * label + epsilon / n
    return (1 - epsilon) * label + epsilon * prior_dist


@def_op("temporal_shift")
def temporal_shift(x, seg_num=1, shift_ratio=0.25, data_format="NCHW"):
    NT, C, H, W = x.shape
    N = NT // seg_num
    xr = x.reshape(N, seg_num, C, H, W)
    c1 = int(C * shift_ratio)
    c2 = int(C * 2 * shift_ratio)
    pad = jnp.zeros_like(xr[:, :1])
    left = jnp.concatenate([xr[:, 1:, :c1], pad[:, :, :c1]], axis=1)
    right = jnp.concatenate([pad[:, :, c1:c2], xr[:, :-1, c1:c2]], axis=1)
    rest = xr[:, :, c2:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(NT, C, H, W)


@def_op("pixel_shuffle")
def pixel_shuffle(x, upscale_factor=1, data_format="NCHW"):
    r = upscale_factor
    N, C, H, W = x.shape
    x = x.reshape(N, C // (r * r), r, r, H, W)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(N, C // (r * r), H * r, W * r)
