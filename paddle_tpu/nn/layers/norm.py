"""Normalization layers (reference: ``python/paddle/nn/layer/norm.py``).

BatchNorm running stats live in registered buffers and are updated
functionally — ``functional_call`` captures the new values, so the jitted
train step carries them as explicit state (no in-place CUDA mutation as in
the reference's ``batch_norm`` kernel).
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import functional as F
from ..initializer import Constant
from ..layer import Layer


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCHW", use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr, default_initializer=Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter((num_features,), attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", jnp.zeros((num_features,), jnp.float32))
        self.register_buffer("_variance", jnp.ones((num_features,), jnp.float32))

    def forward(self, x):
        out, new_mean, new_var = F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format, use_global_stats=self.use_global_stats)
        if self.training and not self.use_global_stats:
            self._mean = new_mean
            self._variance = new_var
        return out

    def extra_repr(self):
        return f"num_features={self.num_features}, momentum={self.momentum}, epsilon={self.epsilon}"


class BatchNorm(_BatchNormBase):
    """Legacy ``paddle.nn.BatchNorm`` (act fused)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05,
                 param_attr=None, bias_attr=None, data_layout="NCHW", use_global_stats=None):
        super().__init__(num_channels, momentum, epsilon, param_attr, bias_attr,
                         data_layout, use_global_stats)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCL", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCDHW", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm. Inside ``shard_map``/``pmap`` the mean/var
    reduce over the mesh 'data' axis (reference: ``sync_batch_norm_op.cu``
    NCCL allreduce of per-GPU stats); under plain pjit, GSPMD already
    computes global stats because the batch axis is just sharded.
    """

    def __init__(self, *args, axis_name=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._axis_name = axis_name

    def forward(self, x):
        import jax

        if self._axis_name is None:
            return super().forward(x)
        ch_axis = 1 if self.data_format.startswith("NC") else x.ndim - 1
        reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
        mean = jnp.mean(x, axis=reduce_axes)
        meansq = jnp.mean(jnp.square(x), axis=reduce_axes)
        mean = jax.lax.pmean(mean, self._axis_name)
        meansq = jax.lax.pmean(meansq, self._axis_name)
        var = meansq - jnp.square(mean)
        shape = [1] * x.ndim
        shape[ch_axis] = -1
        out = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self.epsilon)
        if self.weight is not None:
            out = out * self.weight.reshape(shape)
        if self.bias is not None:
            out = out + self.bias.reshape(shape)
        if self.training:
            n = x.size // x.shape[ch_axis]
            unbiased = var * n / max(n - 1, 1)
            self._mean = self.momentum * self._mean + (1 - self.momentum) * mean
            self._variance = self.momentum * self._variance + (1 - self.momentum) * unbiased
        return out

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Recursively convert BatchNorm* sublayers to SyncBatchNorm."""
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            new = cls(layer.num_features, layer.momentum, layer.epsilon,
                      data_format=layer.data_format)
            new.set_state_dict(layer.state_dict())
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr, default_initializer=Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(self.normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias, self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={self.normalized_shape}, epsilon={self.epsilon}"


class RMSNorm(Layer):
    """Llama-family norm; absent in the reference (see SURVEY §2.3 note on
    missing modern blocks) but required by BASELINE.md's Llama-2 target."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter((hidden_size,), attr=weight_attr,
                                            default_initializer=Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_channels,), attr=weight_attr, default_initializer=Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter((num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.weight, self.bias,
                            self.epsilon, self.data_format)


class InstanceNorm1D(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCL", name=None):
        super().__init__()
        self.num_features = num_features
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is False:
            self.weight = None
            self.bias = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr, default_initializer=Constant(1.0))
            self.bias = self.create_parameter((num_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self.epsilon, data_format=self.data_format)


class InstanceNorm2D(InstanceNorm1D):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__(num_features, epsilon, momentum, weight_attr, bias_attr, data_format)


class InstanceNorm3D(InstanceNorm1D):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCDHW", name=None):
        super().__init__(num_features, epsilon, momentum, weight_attr, bias_attr, data_format)


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k, self.data_format)


class SpectralNorm(Layer):
    """Power-iteration spectral norm (reference: ``spectral_norm_op``)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, name=None):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps
        import numpy as np

        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        from ..initializer import Normal

        self.weight_u = self.create_parameter((h,), default_initializer=Normal(0.0, 1.0))
        self.weight_v = self.create_parameter((w,), default_initializer=Normal(0.0, 1.0))

    def forward(self, weight):
        w = jnp.moveaxis(jnp.asarray(weight), self.dim, 0)
        mat = w.reshape(w.shape[0], -1)
        u, v = self.weight_u, self.weight_v
        for _ in range(self.power_iters):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + self.eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + self.eps)
        sigma = u @ mat @ v
        return jnp.moveaxis((mat / sigma).reshape(w.shape), 0, self.dim)
