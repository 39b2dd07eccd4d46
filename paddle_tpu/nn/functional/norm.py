"""Normalization functionals (python/paddle/nn/functional/norm.py analog).

layer_norm / rms_norm have Pallas fast paths on TPU (paddle_tpu/kernels/);
the jnp forms here are the reference lowering and the CPU fallback — XLA
fuses them into a handful of VPU loops anyway.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.op_registry import register_op
from ...ops._dispatch import apply, as_tensor


@register_op("nn.layer_norm")
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None, spec=None):
    """``spec`` (optional PartitionSpec of ``x``): where the fused kernel's
    rows live under a mesh when it is not the default batch-on-data-axes —
    a model that shards the sequence dim passes its residual-stream spec."""
    x = as_tensor(x)
    nshape = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
    axes = tuple(range(x.ndim - len(nshape), x.ndim))
    tensors = [x]
    if weight is not None:
        tensors.append(as_tensor(weight))
    if bias is not None:
        tensors.append(as_tensor(bias))

    # fused Pallas path (fused layer_norm CUDA-kernel analog): single trailing
    # axis with affine, on TPU
    from ._pallas_gate import use_pallas

    if use_pallas() and len(nshape) == 1 and weight is not None and bias is not None:
        from ...kernels.norms import fused_layer_norm

        return apply("layer_norm_pallas", lambda xv, wv, bv: fused_layer_norm(xv, wv, bv, epsilon, spec), *tensors)

    def fn(xv, *rest):
        x32 = xv.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32)
        return out.astype(xv.dtype)

    return apply("layer_norm", fn, *tensors)


@register_op("nn.rms_norm")
def rms_norm(x, weight=None, epsilon=1e-6, name=None, spec=None):
    x = as_tensor(x)
    tensors = [x] + ([as_tensor(weight)] if weight is not None else [])

    from ._pallas_gate import use_pallas

    if use_pallas() and weight is not None:
        from ...kernels.norms import fused_rms_norm

        return apply("rms_norm_pallas", lambda xv, wv: fused_rms_norm(xv, wv, epsilon, spec), *tensors)

    def fn(xv, *rest):
        x32 = xv.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(ms + epsilon)
        if rest:
            out = out * rest[0].astype(jnp.float32)
        return out.astype(xv.dtype)

    return apply("rms_norm", fn, *tensors)


@register_op("nn.batch_norm")
def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    """Functional batch norm. In training mode, updates running stats in place
    on the running_mean/var tensors (overlay-aware, so jit capture works)."""
    x = as_tensor(x)
    rm, rv = as_tensor(running_mean), as_tensor(running_var)
    ch_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch = training and not use_global_stats

    tensors = [x]
    if weight is not None:
        tensors.append(as_tensor(weight))
    if bias is not None:
        tensors.append(as_tensor(bias))

    if use_batch:
        # update running stats outside the grad path (paddle: running =
        # momentum*running + (1-momentum)*batch); overlay-aware write so the
        # update is captured when tracing under jit.
        x32_stats = x._value.astype(jnp.float32)
        batch_mean = jnp.mean(x32_stats, axis=axes)
        batch_var = jnp.var(x32_stats, axis=axes)
        rm._set_value_raw((momentum * rm._value + (1 - momentum) * batch_mean).astype(rm._value.dtype))
        rv._set_value_raw((momentum * rv._value + (1 - momentum) * batch_var).astype(rv._value.dtype))
        frozen_mean = frozen_var = None
    else:
        frozen_mean, frozen_var = rm._value.astype(jnp.float32), rv._value.astype(jnp.float32)

    def fn(xv, *rest):
        shape = [1] * xv.ndim
        shape[ch_axis] = xv.shape[ch_axis]
        x32 = xv.astype(jnp.float32)
        if use_batch:
            mean = jnp.mean(x32, axis=axes)  # inside the vjp: grads flow through stats
            var = jnp.var(x32, axis=axes)
        else:
            mean, var = frozen_mean, frozen_var
        out = (x32 - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        return out.astype(xv.dtype)

    return apply("batch_norm", fn, *tensors)


@register_op("nn.group_norm")
def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW", name=None):
    x = as_tensor(x)
    tensors = [x]
    if weight is not None:
        tensors.append(as_tensor(weight))
    if bias is not None:
        tensors.append(as_tensor(bias))

    def fn(xv, *rest):
        n, c = xv.shape[0], xv.shape[1]
        spatial = xv.shape[2:]
        x32 = xv.astype(jnp.float32).reshape(n, num_groups, c // num_groups, *spatial)
        axes = tuple(range(2, x32.ndim))
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.var(x32, axis=axes, keepdims=True)
        out = ((x32 - mean) * jax.lax.rsqrt(var + epsilon)).reshape(xv.shape)
        shape = [1] * xv.ndim
        shape[1] = c
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        return out.astype(xv.dtype)

    return apply("group_norm", fn, *tensors)


@register_op("nn.instance_norm")
def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    x = as_tensor(x)
    tensors = [x]
    if weight is not None:
        tensors.append(as_tensor(weight))
    if bias is not None:
        tensors.append(as_tensor(bias))

    def fn(xv, *rest):
        axes = tuple(range(2, xv.ndim))
        x32 = xv.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.var(x32, axis=axes, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + eps)
        shape = [1] * xv.ndim
        shape[1] = xv.shape[1]
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        return out.astype(xv.dtype)

    return apply("instance_norm", fn, *tensors)


@register_op("nn.local_response_norm")
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    x = as_tensor(x)

    def fn(xv):
        sq = jnp.square(xv)
        half = size // 2
        pads = [(0, 0)] * xv.ndim
        pads[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        windows = sum(
            jax.lax.dynamic_slice_in_dim(padded, i, xv.shape[1], axis=1) for i in range(size)
        )
        return xv / jnp.power(k + alpha * windows, beta)

    return apply("local_response_norm", fn, x)


@register_op("nn.spectral_norm_fn")
def spectral_norm(weight, u, v, dim=0, power_iters=1, eps=1e-12):
    weight, u, v = as_tensor(weight), as_tensor(u), as_tensor(v)

    def fn(wv, uv, vv):
        w = jnp.moveaxis(wv, dim, 0).reshape(wv.shape[dim], -1)
        for _ in range(power_iters):
            vv = w.T @ uv
            vv = vv / (jnp.linalg.norm(vv) + eps)
            uv = w @ vv
            uv = uv / (jnp.linalg.norm(uv) + eps)
        sigma = uv @ w @ vv
        return wv / sigma

    return apply("spectral_norm", fn, weight, u, v)
