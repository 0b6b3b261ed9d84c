"""Pooling layers.

Replaces the reference's SubsamplingLayer
(nn/layers/convolution/subsampling/SubsamplingLayer.java) + its cuDNN
helper (CudnnSubsamplingHelper.java) with ``lax.reduce_window`` — XLA
fuses and schedules these natively on TPU. GlobalPoolingLayer mirrors
nn/layers/pooling/GlobalPoolingLayer.java incl. masked time-series
pooling (MaskedReductionUtil semantics).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.conf.layers.convolutional import _pair, _out_dim

__all__ = ["PoolingType", "SubsamplingLayer", "Subsampling1DLayer",
           "GlobalPoolingLayer"]


class PoolingType:
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


@register_layer
@dataclasses.dataclass
class SubsamplingLayer(Layer):
    """2-d pooling (nn/conf/layers/SubsamplingLayer.java)."""

    pooling: str = PoolingType.MAX
    kernel: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def __post_init__(self):
        self.kernel = _pair(self.kernel)
        self.stride = _pair(self.stride)
        self.padding = _pair(self.padding)

    def output_type(self, input_type: InputType) -> InputType:
        h = _out_dim(input_type.height, self.kernel[0], self.stride[0],
                     self.padding[0], self.convolution_mode)
        w = _out_dim(input_type.width, self.kernel[1], self.stride[1],
                     self.padding[1], self.convolution_mode)
        return InputType.convolutional(h, w, input_type.channels)

    def _window_pool(self, x):
        window = (1,) + self.kernel + (1,)
        strides = (1,) + self.stride + (1,)
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            pad = ((0, 0), (self.padding[0], self.padding[0]),
                   (self.padding[1], self.padding[1]), (0, 0))
        if self.pooling == PoolingType.MAX:
            return lax.reduce_window(x, -jnp.inf, lax.max, window, strides,
                                     pad)
        if self.pooling in (PoolingType.AVG, PoolingType.SUM):
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
            if self.pooling == PoolingType.SUM:
                return s
            if self.convolution_mode == "same":
                ones = jnp.ones_like(x)
                counts = lax.reduce_window(ones, 0.0, lax.add, window,
                                           strides, pad)
                return s / counts
            return s / (self.kernel[0] * self.kernel[1])
        if self.pooling == PoolingType.PNORM:
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window,
                                  strides, pad)
            return s ** (1.0 / p)
        raise ValueError(f"Unknown pooling type {self.pooling}")

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return self._window_pool(x), state


@register_layer
@dataclasses.dataclass
class Subsampling1DLayer(SubsamplingLayer):
    """1-d pooling over (B,T,C) (nn/conf/layers/Subsampling1DLayer.java)."""

    def __post_init__(self):
        k = self.kernel[0] if isinstance(self.kernel, (tuple, list)) \
            else self.kernel
        s = self.stride[0] if isinstance(self.stride, (tuple, list)) \
            else self.stride
        p = self.padding[0] if isinstance(self.padding, (tuple, list)) \
            else self.padding
        self.kernel = (int(k), 1)
        self.stride = (int(s), 1)
        self.padding = (int(p), 0)

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        if t is not None:
            t = _out_dim(t, self.kernel[0], self.stride[0], self.padding[0],
                         self.convolution_mode)
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        y = self._window_pool(x[:, :, None, :])[:, :, 0, :]
        return y, state


@register_layer
@dataclasses.dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over spatial (CNN) or time (RNN) dims
    (nn/conf/layers/GlobalPoolingLayer.java). Respects sequence masks
    the way MaskedReductionUtil does: masked steps excluded from
    max/avg/sum."""

    pooling: str = PoolingType.AVG
    pnorm: int = 2
    collapse_dimensions: bool = True

    # under sequence parallelism this layer COLLAPSES the sharded time
    # axis with a collective (pmax/psum/pmean over the seq axis), so
    # downstream layers see replicated activations — the wrapper's
    # validation lets any layer follow it (Layer base declares False)
    seq_collapses_time = True

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.feed_forward(input_type.size)
        if input_type.kind == "cnn":
            return InputType.feed_forward(input_type.channels)
        return input_type

    @staticmethod
    def _combine(val, seq_ax, op):
        """Combine local pools across the seq axis, then re-mark the
        (now identical-everywhere) result as device-varying: the seq
        step's loss pmean and the /nshards gradient normalization
        count one term per shard, so the collective's output must
        keep the varying type (each shard's identical copy IS its
        term)."""
        if not seq_ax:
            return val
        if op is lax.pmax:
            # pmax has no differentiation rule: gather + max instead
            # (gradient flows to the argmax shard's local pool); the
            # gathered result already carries the varying type
            return jnp.max(lax.all_gather(val, seq_ax), axis=0)
        # psum/pmean outputs are seq-INVARIANT: re-mark varying
        return lax.pcast(op(val, seq_ax), seq_ax, to="varying")

    def apply_stream(self, params, cache, x):
        """Stateful streaming inference (the rnnTimeStep contract
        extended through the time collapse): the carry is the running
        pool statistic — sum+count (avg), max, sum, or Σ|x|^p
        (pnorm). Each step returns the pool over the stream SO FAR,
        so the final step equals the full-sequence ``apply`` and a
        prefix step is the prediction on that prefix."""
        if x.ndim != 3:
            raise ValueError("apply_stream pools over TIME: input "
                             f"must be (B, t, C), got {x.shape}")
        if self.pooling == PoolingType.MAX:
            cur = jnp.max(x, axis=1)
            m = cur if cache is None else jnp.maximum(cache, cur)
            return m, m
        if self.pooling in (PoolingType.AVG, PoolingType.SUM):
            s_new = jnp.sum(x, axis=1)
            n_new = x.shape[1]
            if cache is not None:
                s_new = s_new + cache["sum"]
                n_new = n_new + cache["count"]
            cache = {"sum": s_new, "count": n_new}
            if self.pooling == PoolingType.SUM:
                return s_new, cache
            return s_new / n_new, cache
        if self.pooling == PoolingType.PNORM:
            p = float(self.pnorm)
            s_new = jnp.sum(jnp.abs(x) ** p, axis=1)
            if cache is not None:
                s_new = s_new + cache
            return s_new ** (1.0 / p), s_new
        raise ValueError(self.pooling)

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        from deeplearning4j_tpu.parallel.seq_context import (
            current_seq_axis)
        if x.ndim == 4:          # NHWC → pool over H,W
            axes = (1, 2)
            seq_ax = None
        elif x.ndim == 3:        # NTC → pool over T
            axes = (1,)
            # sequence-parallel: x is the LOCAL time chunk — pool
            # locally, then combine across the seq axis so every
            # shard holds the GLOBAL pool (replicated downstream)
            seq_ax = current_seq_axis()
        else:
            return x, state
        if mask is not None and x.ndim == 3:
            m = mask[..., None]          # (B,T,1)
            if self.pooling == PoolingType.MAX:
                big_neg = jnp.finfo(x.dtype).min
                out = jnp.max(jnp.where(m > 0, x, big_neg), axis=1)
                return self._combine(out, seq_ax, lax.pmax), state
            if self.pooling == PoolingType.SUM:
                out = jnp.sum(x * m, axis=1)
                return self._combine(out, seq_ax, lax.psum), state
            if self.pooling == PoolingType.AVG:
                # global masked mean: combine numerator AND count
                num = self._combine(jnp.sum(x * m, axis=1), seq_ax,
                                    lax.psum)
                den = self._combine(jnp.sum(m, axis=1), seq_ax,
                                    lax.psum)
                return num / jnp.maximum(den, 1.0), state
            if self.pooling == PoolingType.PNORM:
                p = float(self.pnorm)
                s = jnp.sum((jnp.abs(x) * m) ** p, axis=1)
                s = self._combine(s, seq_ax, lax.psum)
                return s ** (1.0 / p), state
        if self.pooling == PoolingType.MAX:
            out = jnp.max(x, axis=axes)
            return self._combine(out, seq_ax, lax.pmax), state
        if self.pooling == PoolingType.AVG:
            out = jnp.mean(x, axis=axes)     # equal chunks: pmean exact
            return self._combine(out, seq_ax, lax.pmean), state
        if self.pooling == PoolingType.SUM:
            out = jnp.sum(x, axis=axes)
            return self._combine(out, seq_ax, lax.psum), state
        if self.pooling == PoolingType.PNORM:
            p = float(self.pnorm)
            s = jnp.sum(jnp.abs(x) ** p, axis=axes)
            s = self._combine(s, seq_ax, lax.psum)
            return s ** (1.0 / p), state
        raise ValueError(self.pooling)
