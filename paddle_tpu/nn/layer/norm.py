"""Normalization layers (python/paddle/nn/layer/norm.py analog)."""

from __future__ import annotations

import jax.numpy as jnp

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .layers import Layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.normalized_shape = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(self.normalized_shape, attr=None if weight_attr in (None, True) else weight_attr, default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(self.normalized_shape, attr=None if bias_attr in (None, True) else bias_attr, is_bias=True)

    def forward(self, x, spec=None):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias, self.epsilon, spec=spec)

    def extra_repr(self):
        return f"normalized_shape={list(self.normalized_shape)}, epsilon={self.epsilon}"


class RMSNorm(Layer):
    """RMS normalization — first-class here (the reference gained it later);
    the transformer stack defaults to it for TPU-friendly fusion."""

    def __init__(self, normalized_shape, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        shape = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
        self.epsilon = epsilon
        self.weight = self.create_parameter(shape, attr=None if weight_attr in (None, True) else weight_attr, default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCHW", use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum, self._epsilon = momentum, epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter([num_features], attr=None if weight_attr in (None, True) else weight_attr, default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter([num_features], attr=None if bias_attr in (None, True) else bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros([num_features], jnp.float32)))
        self.register_buffer("_variance", Tensor(jnp.ones([num_features], jnp.float32)))

    def forward(self, x):
        return F.batch_norm(
            x,
            self._mean,
            self._variance,
            weight=self.weight,
            bias=self.bias,
            training=self.training,
            momentum=self._momentum,
            epsilon=self._epsilon,
            data_format=self._data_format,
            use_global_stats=self._use_global_stats,
        )

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}, epsilon={self._epsilon}"


class BatchNorm(_BatchNormBase):
    pass


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN. Under GSPMD data parallelism the batch axis is sharded
    and XLA computes global batch statistics automatically when the reduction
    spans the full array — so SyncBatchNorm == BatchNorm in the pjit regime
    (the reference needs a dedicated NCCL kernel, sync_batch_norm_op).
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        return layer


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups, self._num_channels, self._epsilon = num_groups, num_channels, epsilon
        self.weight = None if weight_attr is False else self.create_parameter([num_channels], default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter([num_channels], is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight, self.bias)


class InstanceNorm1D(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None, bias_attr=None, data_format="NCL", name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter([num_features], default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter([num_features], is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias, eps=self._epsilon)


class InstanceNorm2D(InstanceNorm1D):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__(num_features, epsilon, momentum, weight_attr, bias_attr)


class InstanceNorm3D(InstanceNorm1D):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None, bias_attr=None, data_format="NCDHW", name=None):
        super().__init__(num_features, epsilon, momentum, weight_attr, bias_attr)


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12, name=None):
        super().__init__()
        self._dim, self._power_iters, self._epsilon = dim, power_iters, epsilon
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        self.weight_u = self.create_parameter([h], default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter([w], default_initializer=I.Normal(0.0, 1.0))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        return F.norm.spectral_norm(weight, self.weight_u, self.weight_v, dim=self._dim, power_iters=self._power_iters, eps=self._epsilon)
