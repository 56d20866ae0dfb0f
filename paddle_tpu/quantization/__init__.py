"""paddle_tpu.quantization — QAT fake-quant + PTQ calibration.

Reference parity: ``python/paddle/quantization/`` (QuantConfig, QAT, PTQ,
observer/quanter registry) and the imperative engine
(``fluid/contrib/slim/quantization/imperative/qat.py`` —
ImperativeQuantAware wrapping Conv2D/Linear with FakeQuant*). TPU-native:
fake-quant is a straight-through-estimator ``custom_vjp`` (the CUDA
``fake_quantize_*`` kernels collapse to a few jnp ops); observer state
lives in Layer buffers so QAT traces under jit like BatchNorm stats.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Type

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from ..nn.layer import Layer

__all__ = [
    "fake_quant", "quant_dequant", "AbsmaxObserver",
    "MovingAverageAbsmaxObserver", "QuantConfig", "QAT", "PTQ",
    "QuantedLinear", "QuantedConv2D",
    "QuantedColumnParallelLinear", "QuantedRowParallelLinear",
    "kv_quantize", "kv_dequantize", "is_quantized_kv",
]


# --------------------------------------------------------------- fake quant
import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def quant_dequant(x, scale, bits: int = 8):
    """Simulated quantization: round(x / s * qmax) * s / qmax, clipped.
    Straight-through gradient (reference ``fake_quantize_dequantize_
    moving_average_abs_max`` op)."""
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x / s * qmax), -qmax - 1, qmax)
    return q * s / qmax


def _qdq_fwd(x, scale, bits=8):
    return quant_dequant(x, scale, bits), (x, scale)


def _qdq_bwd(bits, res, g):
    x, scale = res
    # STE: pass-through inside the clip range, zero outside
    inside = (jnp.abs(x) <= jnp.maximum(scale, 1e-8)).astype(g.dtype)
    return g * inside, jnp.zeros_like(scale)


quant_dequant.defvjp(_qdq_fwd, _qdq_bwd)
fake_quant = quant_dequant


# ----------------------------------------------------- int8 KV-cache quant
# The decode engines store KV-cache entries as either a plain array
# [B, S, Hkv, D] or, under ``kv_dtype="int8"``, a ``(values, scales)``
# pair: int8 values plus per-(row, position, head) float32 abs-max scales
# [B, S, Hkv, 1]. Keeping the scale 4-D (trailing axis 1 instead of a
# squeezed [B, S, Hkv]) means every cache pytree primitive in
# ``models/kv_cache.py`` — row scatter, block gather/scatter,
# sharding constraints — works on both leaves unchanged via jax.tree
# maps. Symmetric quantization to ±127 so dequant is a single multiply.

KV_QUANT_EPS = 1e-8


def kv_quantize(x, eps: float = KV_QUANT_EPS):
    """Quantize ``x`` [..., D] to ``(int8 values, float32 scales)`` with a
    per-head abs-max scale over the trailing (head_dim) axis. All-zero
    heads get the ``eps`` floor so dequant stays exact-zero instead of
    0/0."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, eps) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype=jnp.float32):
    """Inverse of :func:`kv_quantize`: ``q * scale`` cast to ``dtype``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def is_quantized_kv(entry) -> bool:
    """True when a cache entry is a quantized ``(int8 values, scales)``
    pair rather than a plain full-precision array."""
    return (isinstance(entry, (tuple, list)) and len(entry) == 2
            and getattr(entry[0], "dtype", None) == jnp.int8)


# ---------------------------------------------------------------- observers
class AbsmaxObserver:
    """Per-tensor abs-max (reference ``AbsmaxQuantizer`` PTQ observer)."""

    def init_state(self):
        return jnp.zeros((), jnp.float32)

    def update(self, state, x):
        return jnp.maximum(state, jnp.abs(x).max().astype(jnp.float32))

    def scale(self, state):
        return state


class MovingAverageAbsmaxObserver:
    """EMA abs-max (QAT default, reference ``moving_average_abs_max``)."""

    def __init__(self, momentum: float = 0.9):
        self.momentum = momentum

    def init_state(self):
        return jnp.zeros((), jnp.float32)

    def update(self, state, x):
        cur = jnp.abs(x).max().astype(jnp.float32)
        # first update adopts the current max outright
        return jnp.where(state == 0, cur,
                         self.momentum * state + (1 - self.momentum) * cur)

    def scale(self, state):
        return state


class QuantConfig:
    """Which observer quantizes activations, and at what width (reference
    ``paddle.quantization.QuantConfig`` reduced to the functional fields).
    Weights always use fresh per-forward abs-max (the reference's
    ``fake_quantize_dequantize_abs_max``), so ``weight`` is accepted only
    for signature parity."""

    def __init__(self, activation=None, weight=None, bits: int = 8):
        self.activation = activation or MovingAverageAbsmaxObserver()
        self.weight = weight
        self.bits = bits


# ------------------------------------------------------------ quanted layers
class _QuantedBase(Layer):
    def __init__(self, inner: Layer, config: QuantConfig):
        super().__init__()
        self.inner = inner
        self.config = config
        self._frozen = False       # set by PTQ.convert: scales stop updating
        self._calibrating = False  # PTQ: observe in eval mode (dropout/BN
        #                            must behave as inference during calib)
        self.register_buffer("act_scale_state",
                             config.activation.init_state())

    def _observe_and_quant(self, x, weight):
        cfg = self.config
        if (self.training or self._calibrating) and not self._frozen:
            self.act_scale_state = cfg.activation.update(
                self.act_scale_state, x)
        act_scale = cfg.activation.scale(self.act_scale_state)
        # uncalibrated (scale 0) -> pass activations through unquantized
        # rather than collapsing everything to ~0
        xq = jnp.where(act_scale > 0,
                       quant_dequant(x, act_scale, cfg.bits), x)
        # weights: fresh abs-max every forward (reference
        # fake_quantize_dequantize_abs_max recomputes per call, so the
        # scale tracks shrinking weights under decay)
        w_scale = jnp.abs(weight).max().astype(jnp.float32)
        wq = quant_dequant(weight, w_scale, cfg.bits)
        return xq, wq

    # LoRA targets layers by (in_features, out_features); delegate so an
    # adapter can inject onto a quantized base projection
    @property
    def in_features(self):
        return self.inner.in_features

    @property
    def out_features(self):
        return self.inner.out_features


class QuantedLinear(_QuantedBase):
    def forward(self, x):
        xq, wq = self._observe_and_quant(x, self.inner.weight)
        return F.linear(xq, wq, self.inner.bias)


class QuantedConv2D(_QuantedBase):
    def forward(self, x):
        xq, wq = self._observe_and_quant(x, self.inner.weight)
        c = self.inner
        return F.conv2d(xq, wq, c.bias, c.stride, c.padding, c.dilation,
                        c.groups, c.data_format)


class QuantedColumnParallelLinear(_QuantedBase):
    """Fake-quant wrapper for the mp-sharded projections GPT/Llama decoder
    blocks are built from (the PTQ path a small draft model takes before
    serving). Per-shard abs-max weight scale — same locality as the
    inner layer's sharding."""

    def forward(self, x):
        from ..distributed.parallel.mp_layers import _constrain

        xq, wq = self._observe_and_quant(x, self.inner.weight)
        out = F.linear(xq, wq, self.inner.bias)
        if self.inner.gather_output:
            return _constrain(out, "dp", None, None)
        return _constrain(out, "dp", None, "mp")


class QuantedRowParallelLinear(_QuantedBase):
    def forward(self, x):
        from ..distributed.parallel.mp_layers import _constrain

        if self.inner.input_is_parallel:
            x = _constrain(x, "dp", None, "mp")
        xq, wq = self._observe_and_quant(x, self.inner.weight)
        out = jnp.matmul(xq, wq)
        if self.inner.bias is not None:
            out = out + self.inner.bias
        return _constrain(out, "dp", None, None)


def _quantable() -> Dict[Type[Layer], Type[_QuantedBase]]:
    from ..distributed.parallel.mp_layers import (ColumnParallelLinear,
                                                  RowParallelLinear)

    table = dict(_QUANTABLE)
    table[ColumnParallelLinear] = QuantedColumnParallelLinear
    table[RowParallelLinear] = QuantedRowParallelLinear
    return table


_QUANTABLE: Dict[Type[Layer], Type[_QuantedBase]] = {
    nn.Linear: QuantedLinear,
    nn.Conv2D: QuantedConv2D,
}


def _swap_layers(layer: Layer, config: QuantConfig, table=None) -> None:
    table = _quantable() if table is None else table
    for name, sub in list(layer._sub_layers.items()):
        if sub is None:
            continue
        cls = table.get(type(sub))
        if cls is not None:
            layer._sub_layers[name] = cls(sub, config)
        else:
            _swap_layers(sub, config, table)


class QAT:
    """Quantization-aware training driver (reference ``paddle.quantization.
    QAT`` / ``ImperativeQuantAware.quantize``): swaps quantable layers for
    fake-quant wrappers; train as usual, observers ride the buffers."""

    def __init__(self, config: Optional[QuantConfig] = None):
        self.config = config or QuantConfig()

    def quantize(self, model: Layer) -> Layer:
        cls = _quantable().get(type(model))
        if cls is not None:
            return cls(model, self.config)
        _swap_layers(model, self.config)
        return model


class PTQ:
    """Post-training quantization: calibrate with sample batches, then
    freeze scales (reference ``paddle.quantization.PTQ``)."""

    def __init__(self, config: Optional[QuantConfig] = None):
        self.config = config or QuantConfig(activation=AbsmaxObserver())

    @staticmethod
    def _walk_quanted(layer):
        if isinstance(layer, _QuantedBase):
            yield layer
        for sub in layer._sub_layers.values():
            if sub is not None:
                yield from PTQ._walk_quanted(sub)

    def quantize(self, model: Layer) -> Layer:
        model = QAT(self.config).quantize(model)
        # calibration runs in eval mode (dropout off, BN uses running
        # stats — inference-time activation ranges are what we calibrate
        # against); observers record via the _calibrating flag
        model.eval()
        for q in self._walk_quanted(model):
            q._calibrating = True
        return model

    def convert(self, model: Layer) -> Layer:
        """Freeze scales at their calibrated values — permanent, not a
        train/eval mode flag: later ``train()`` calls won't resume
        observer updates."""
        for q in self._walk_quanted(model):
            q._frozen = True
            q._calibrating = False
        model.eval()
        return model
