"""LFM2's gated short convolution (``Lfm2ShortConv`` of the ``lfm2`` /
``lfm2_moe`` configs): a depthwise causal convolution of a few
positions between two gates, in place of attention in most layers of
the model.

For one token ``n`` (B, C) of width ``D`` and K the convolution's
width (``conv_L_cache``):

  [B | C | x] = n W_in               three chunks of D, in that order
  u_t = B_t * x_t
  c_t = sum_k w[k] * u_{t-K+1+k}     channel by channel, zeros before
                                     position 0; no bias, no activation
  o_t = (C_t * c_t) W_out

What a stream carries from one token to the next is the last K - 1
``u``: K - 1 rows of D values whatever its length. ``apply`` runs over
a whole sequence; ``apply_stream_paged`` is the serving step over a
pool with one row a SLOT, under the contract
``Mamba2MixerLayer.apply_stream_paged`` states.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.paged import (STATE, PagedCache,
                                                     PagedLayer)
from deeplearning4j_tpu.nn.conf.layers.state_space import carried_window

__all__ = ["ShortConvMixerLayer"]

_F32 = jnp.float32


@register_layer
@dataclasses.dataclass
class ShortConvMixerLayer(PagedLayer, BaseLayer):
    """Gated short convolution, (B,T,C) -> (B,T,C); ``conv_width`` is
    the source's ``conv_L_cache``. No bias.

    Parameters: ``W_in`` (D, 3 D), ``conv_w`` (K, D) (the source's
    (D, 1, K) with the channels last, where the device's lanes are),
    ``W_out`` (D, D), in the policy's parameter dtype; the gates and
    the convolution's sum run in float32, and ``u`` is rounded to the
    parameters' dtype where it is made, so that the window a stream
    keeps holds what the step that made it used."""

    n_in: Optional[int] = None
    conv_width: int = 3

    def __post_init__(self):
        if self.conv_width < 2:
            raise ValueError(
                f"conv_width must be >= 2, got {self.conv_width}")

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        """The convolution uniform within 1 / sqrt(K), as the source's
        depthwise ``Conv1d``."""
        self.set_n_in(input_type)
        d, K = self.n_in, self.conv_width
        ks = jax.random.split(key, 3)
        return {
            "W_in": self._sample_w(ks[0], (d, 3 * d), d, 3 * d),
            "conv_w": jax.random.uniform(
                ks[1], (K, d), _F32, -K ** -0.5, K ** -0.5
            ).astype(dtypes.policy().param_dtype),
            "W_out": self._sample_w(ks[2], (d, d), d, d),
        }, {}

    def _mix(self, params, window, x):
        """x (B,t,C) behind the K - 1 inputs before it, ``window``
        (B,K-1,D): ``(out (B,t,C), the inputs with their window in
        front (B,K-1+t,D))``."""
        t, d, w = x.shape[1], self.n_in, params["W_in"]
        p = einsum_f32("btc,cn->btn", x.astype(w.dtype), w)
        with jax.named_scope("window"):
            u = (p[..., :d] * p[..., 2 * d:]).astype(w.dtype)
            xs = jnp.concatenate([window.astype(w.dtype), u], axis=1)
            taps = params["conv_w"].astype(_F32)
            c = taps[0] * xs[:, :t].astype(_F32)
            for k in range(1, self.conv_width):
                c = c + taps[k] * xs[:, k:k + t].astype(_F32)
            gated = (p[..., d:2 * d] * c).astype(w.dtype)
        return gated @ params["W_out"], xs

    # ---- full sequence ----
    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        if mask is not None:
            raise NotImplementedError(
                "ShortConvMixerLayer has no padding mask: feed "
                "sequences of one length")
        x = self.apply_input_dropout(x, training=training, rng=rng)
        window = jnp.zeros((x.shape[0], self.conv_width - 1, self.n_in),
                           params["W_in"].dtype)
        return self._mix(params, window, x)[0], state

    # ---- the serving step: a pool with one row a slot ----
    def paged_cache(self, page_size: int) -> PagedCache:
        return PagedCache(STATE)

    def zero_pool(self, slots: int, page_size: int, dtype):
        """{'conv': (slots, K - 1, D) ``dtype``}: row ``s`` belongs to
        slot ``s`` (``Mamba2MixerLayer.zero_pool``)."""
        return {"conv": jnp.zeros((slots, self.conv_width - 1,
                                   self.n_in), dtype)}

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One step for all slots, the contract of
        ``Mamba2MixerLayer.apply_stream_paged``: row ``s`` of ``x``
        (S,t,C) is slot ``s``, which feeds its first ``n_valid[s]``
        rows; a slot whose ``pos`` is 0 starts from zeros by POSITION
        whatever its row holds; a slot that feeds no row (the
        all-zero table row in the single-row program) keeps its
        window; the window the next step finds is the K - 1 inputs
        before row ``n_valid``. Returns (out, pool)."""
        if n_valid is None:
            n_valid = jnp.where(table[:, 0] > 0, x.shape[1], 0)
        # a select, so that whatever a fresh slot's row holds, even a
        # non-finite value, is dropped
        window = jnp.where((pos == 0)[:, None, None], 0, pool["conv"])
        out, xs = self._mix(params, window, x)
        with jax.named_scope("window"):
            pool = {"conv": carried_window(pool["conv"], xs, n_valid)}
        return out, pool
