"""Normalization layers.

BatchNormalization replaces both the reference's Java impl
(nn/layers/normalization/BatchNormalization.java) and its cuDNN helper
(CudnnBatchNormalizationHelper.java). Running mean/var live in the
layer *state* pytree and are updated functionally at train time — the
executor threads state through the jitted train step (no mutation, no
workspaces).

LocalResponseNormalization mirrors
nn/layers/normalization/LocalResponseNormalization.java /
CudnnLocalResponseNormalizationHelper.java (AlexNet-era LRN).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    BaseLayer, Layer, register_layer,
)

__all__ = ["BatchNormalization", "LayerNormalization",
           "RMSNormalization", "LocalResponseNormalization"]


@register_layer
@dataclasses.dataclass
class BatchNormalization(BaseLayer):
    """(nn/conf/layers/BatchNormalization.java). Normalizes over batch
    (+spatial for CNN input); gamma/beta trainable unless ``lock_gamma_beta``.
    ``decay`` matches the reference's running-average decay (default 0.9)."""

    n_out: Optional[int] = None      # inferred from input type
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    gamma: float = 1.0
    beta: float = 0.0

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_out is None:
            if input_type.kind == "cnn":
                self.n_out = input_type.channels
            else:
                self.n_out = input_type.flat_size()

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        pd = dtypes.policy().param_dtype
        n = self.n_out
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.full((n,), self.gamma, pd),
                      "beta": jnp.full((n,), self.beta, pd)}
        state = {"mean": jnp.zeros((n,), jnp.float32),
                 "var": jnp.ones((n,), jnp.float32)}
        return params, state

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))   # all but channel/feature axis
        if training:
            # single-pass statistics: var = E[x²] − E[x]² lets XLA fuse
            # both reductions into one sweep. ALWAYS in float32 — in
            # bf16 the subtraction catastrophically cancels whenever
            # |mean|/std ≳ 16 (flax BatchNorm makes the same choice)
            xs = jnp.asarray(x, jnp.float32)
            mean = jnp.mean(xs, axis=axes)
            mean_sq = jnp.mean(jnp.square(xs), axis=axes)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (x - mean) * inv
        if not self.lock_gamma_beta:
            y = y * params["gamma"] + params["beta"]
        else:
            y = y * self.gamma + self.beta
        return self.activation_fn()(y), new_state


@register_layer
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """Across-channel LRN (nn/conf/layers/LocalResponseNormalization.java):
    y = x / (k + alpha * sum_{j in window} x_j^2)^beta."""

    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75
    n: int = 5

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        # channel-last; windowed sum of squares over channel axis
        sq = x * x
        half = self.n // 2
        window = (1,) * (x.ndim - 1) + (self.n,)
        strides = (1,) * x.ndim
        pad = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, pad)
        return x / (self.k + self.alpha * ssum) ** self.beta, state


def layer_norm(x, gamma, beta, eps=1e-5):
    """Canonical last-axis layer norm — shared by the standalone
    LayerNormalization layer and TransformerEncoderLayer's inlined
    pre-LN blocks (one implementation, no drift)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * gamma + beta


@register_layer
@dataclasses.dataclass
class LayerNormalization(Layer):
    """Per-example feature normalization (Ba et al. 2016): normalize
    over the LAST axis with learned gamma/beta. Stateless (unlike
    BatchNormalization — no running stats), so it composes with every
    parallelism mode including sequence sharding (pointwise in time)
    and the device-resident pipeline. The reference predates LN; this
    is a capability extension matching the Keras/transformer-era
    surface (TransformerEncoderLayer inlines the same math)."""

    n_in: Optional[int] = None
    eps: float = 1e-5

    seq_parallelizable = True          # per-token normalization

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        pd = dtypes.policy().param_dtype
        return {"gamma": jnp.ones((self.n_in,), pd),
                "beta": jnp.zeros((self.n_in,), pd)}, {}

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        return layer_norm(x, params["gamma"], params["beta"],
                          self.eps), state


def rms_norm(x, gain, eps=1e-6):
    """Last-axis root-mean-square norm (Zhang & Sennrich 2019): no
    mean, no bias. The statistic is float32 whatever ``x`` is (a
    bfloat16 mean of squares loses the small rows); the result comes
    back in ``x``'s dtype. Shared by RMSNormalization and the decoder
    block's inlined norms."""
    xf = jnp.asarray(x, jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                    + eps)
    return (xf * inv * jnp.asarray(gain, jnp.float32)).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class RMSNormalization(Layer):
    """``x / rms(x) * gain`` over the LAST axis: the norm of the
    DeepSeek / Llama line of decoders, and the final norm before
    their output head. Stateless and per token, like
    LayerNormalization."""

    n_in: Optional[int] = None
    eps: float = 1e-6

    seq_parallelizable = True

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        return {"gain": jnp.ones((self.n_in,),
                                 dtypes.policy().param_dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        return rms_norm(x, params["gain"], self.eps), state
