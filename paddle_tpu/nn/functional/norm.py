"""Normalization functionals (reference: python/paddle/nn/functional/norm.py)."""
from __future__ import annotations

import jax.numpy as jnp

from ...core.dispatch import no_grad, register_op
from ...ops._helpers import _op, static_int_list

__all__ = ["batch_norm", "layer_norm", "instance_norm", "group_norm",
           "local_response_norm", "normalize", "add_dropout_ln"]


def _bn_fwd(x, mean, var, weight=None, bias=None, epsilon=1e-5, channel_axis=1,
            has_affine=True):
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    inv = jnp.reciprocal(jnp.sqrt(var.reshape(shape) + epsilon))
    out = (x - mean.reshape(shape)) * inv
    if has_affine:
        out = out * weight.reshape(shape) + bias.reshape(shape)
    return out


register_op("batch_norm_infer", _bn_fwd)


def _bn_train_fwd(x, weight=None, bias=None, epsilon=1e-5, channel_axis=1,
                  has_affine=True):
    axes = tuple(i for i in range(x.ndim) if i != channel_axis)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    inv = jnp.reciprocal(jnp.sqrt(var.reshape(shape) + epsilon))
    out = (x - mean.reshape(shape)) * inv
    if has_affine:
        out = out * weight.reshape(shape) + bias.reshape(shape)
    return out, mean, var


register_op("batch_norm_train", _bn_train_fwd)


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None,
               name=None):
    channel_axis = 1 if data_format.startswith("NC") or x.ndim <= 2 else x.ndim - 1
    if x.ndim <= 2:
        channel_axis = x.ndim - 1
    has_affine = weight is not None
    if use_global_stats is None:
        use_global_stats = not training
    if not use_global_stats:
        args = [x] + ([weight, bias] if has_affine else [])
        out, batch_mean, batch_var = _op("batch_norm_train", *args,
                                         epsilon=float(epsilon),
                                         channel_axis=int(channel_axis),
                                         has_affine=has_affine)
        if running_mean is not None:
            with no_grad():
                m = float(momentum)
                n = 1
                for i, s in enumerate(x.shape):
                    if i != channel_axis:
                        n *= s
                unbiased = batch_var * (n / max(n - 1, 1))
                # Tensor-level arithmetic (not .value() math): under deferred
                # eager the update records into the lazy graph instead of
                # forcing a flush per BN layer
                new_mean = running_mean * m + batch_mean * (1 - m)
                new_var = running_var * m + unbiased * (1 - m)
                running_mean._set_value_inplace(
                    new_mean._data.astype(running_mean.dtype))
                running_var._set_value_inplace(
                    new_var._data.astype(running_var.dtype))
        return out
    args = [x, running_mean, running_var] + ([weight, bias] if has_affine else [])
    return _op("batch_norm_infer", *args, epsilon=float(epsilon),
               channel_axis=int(channel_axis), has_affine=has_affine)


def _layer_norm_fwd(x, weight=None, bias=None, epsilon=1e-5, begin_axis=1,
                    has_scale=True, has_bias=True):
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jnp.reciprocal(jnp.sqrt(var + epsilon))
    shape = x.shape[begin_axis:]
    if has_scale:
        out = out * weight.reshape(shape)
    if has_bias:
        out = out + bias.reshape(shape)
    return out


register_op("layer_norm", _layer_norm_fwd)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    norm_shape = static_int_list(normalized_shape)
    begin_axis = x.ndim - len(norm_shape)
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return _op("layer_norm", *args, epsilon=float(epsilon), begin_axis=int(begin_axis),
               has_scale=weight is not None, has_bias=bias is not None)


def _instance_norm_fwd(x, weight=None, bias=None, epsilon=1e-5, has_affine=True):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jnp.reciprocal(jnp.sqrt(var + epsilon))
    if has_affine:
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape) + bias.reshape(shape)
    return out


register_op("instance_norm", _instance_norm_fwd)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW",
                  name=None):
    args = [x] + ([weight, bias] if weight is not None else [])
    return _op("instance_norm", *args, epsilon=float(eps),
               has_affine=weight is not None)


def _group_norm_fwd(x, weight=None, bias=None, epsilon=1e-5, num_groups=1,
                    has_affine=True, channel_axis=1):
    n = x.shape[0]
    c = x.shape[channel_axis]
    if channel_axis != 1:
        x_m = jnp.moveaxis(x, channel_axis, 1)
    else:
        x_m = x
    spatial = x_m.shape[2:]
    g = num_groups
    xg = x_m.reshape((n, g, c // g) + spatial)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * jnp.reciprocal(jnp.sqrt(var + epsilon))).reshape(x_m.shape)
    if has_affine:
        shape = [1, c] + [1] * (x_m.ndim - 2)
        out = out * weight.reshape(shape) + bias.reshape(shape)
    if channel_axis != 1:
        out = jnp.moveaxis(out, 1, channel_axis)
    return out


register_op("group_norm", _group_norm_fwd)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    args = [x] + ([weight, bias] if weight is not None else [])
    return _op("group_norm", *args, epsilon=float(epsilon), num_groups=int(num_groups),
               has_affine=weight is not None, channel_axis=channel_axis)


def _lrn_fwd(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    # NCHW: normalize across channel windows
    c = x.shape[1]
    sq = jnp.square(x)
    pad_lo = (size - 1) // 2
    pad_hi = size - 1 - pad_lo
    padded = jnp.pad(sq, [(0, 0), (pad_lo, pad_hi)] + [(0, 0)] * (x.ndim - 2))
    acc = jnp.zeros_like(x)
    for i in range(size):
        acc = acc + jnp.take(padded, jnp.arange(i, i + c), axis=1)
    div = jnp.power(k + alpha * acc, beta)
    return x / div


register_op("local_response_norm", _lrn_fwd)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return _op("local_response_norm", x, size=int(size), alpha=float(alpha),
               beta=float(beta), k=float(k))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return _op("normalize", x, p=float(p), axis=int(axis), epsilon=float(epsilon))


def _normalize_fwd(x, p=2.0, axis=1, epsilon=1e-12):
    norm = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(norm, epsilon)


register_op("normalize", _normalize_fwd)


# ---------------------------------------------- fused residual add+dropout+LN


def _add_dropout_ln_fwd(x, sub, weight, bias, seed, rate=0.0, eps=1e-12):
    from ...kernels.pallas.fused_residual import fused_add_dropout_ln
    shape = x.shape
    h = shape[-1]
    n = 1
    for s in shape[:-1]:
        n *= s
    seed = jnp.atleast_1d(seed).astype(jnp.int32)
    out = fused_add_dropout_ln(x.reshape(n, h), sub.reshape(n, h),
                               weight, bias, seed, float(rate), float(eps))
    return out.reshape(shape)


register_op("fused_add_dropout_ln", _add_dropout_ln_fwd, nondiff_inputs=(4,))


def add_dropout_ln(x, sub, weight, bias, p=0.0, epsilon=1e-12, training=True):
    """out = LayerNorm(x + dropout(sub)) — the transformer sublayer residual
    epilogue, fused into one Pallas pass on TPU (kernels/pallas/
    fused_residual.py: in-kernel PRNG mask, row-stat-only saves, one-pass
    backward). Reference analog: operators/fused/fused_attention_op.cu /
    fused_feedforward_op.cu epilogues. Falls back to the unfused
    composition off-TPU (identical semantics, shared dropout-mask source
    excepted)."""
    import os

    from ...core import random as _rng
    from ...core.tensor import Tensor as _T
    from ...kernels.pallas.fused_residual import fused_ln_path_available
    rate = float(p) if training else 0.0
    if (fused_ln_path_available(x, rate)
            and not os.environ.get("PADDLE_DISABLE_FUSED_LN")):
        # rate==0 reuses one cached device constant: each fresh tiny
        # host->device array is a transfer of its own (see lazy.scalar_const)
        from ...core.lazy import scalar_const
        seed = _rng.int32_seed() if rate > 0.0 else scalar_const(0)
        return _op("fused_add_dropout_ln", x, sub, weight, bias, _T(seed),
                   rate=rate, eps=float(epsilon))
    from .common import dropout as _dropout
    h = x + _dropout(sub, p=rate, training=rate > 0.0)
    return layer_norm(h, x.shape[-1], weight, bias, epsilon=epsilon)
