"""Output layers: loss-bearing heads.

Reference: nn/conf/layers/OutputLayer.java / RnnOutputLayer.java /
LossLayer.java / CenterLossOutputLayer.java; impls under
nn/layers/BaseOutputLayer.java, nn/layers/training/.

Each output layer is a Dense-like transform + activation, plus a
``loss_fn(labels, activations, mask)`` hook used by the executors to
assemble the total training loss (score). Stable fused
softmax/sigmoid+CE paths are used when activation/loss pairs match.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    FeedForwardLayer, BaseLayer, register_layer,
)

__all__ = ["OutputLayer", "RnnOutputLayer", "LossLayer",
           "CenterLossOutputLayer"]


def _stable_ce(logits, labels, mask, kind):
    """Fused log-softmax / log-sigmoid cross-entropy (per-example).
    Half-precision logits are promoted to f32: under the bf16 policy
    the hidden activations are bfloat16 (MXU/HBM-native) but exp/log
    at the loss must not be. promote_half never DOWNcasts — the f64
    gradient checker must stay f64."""
    logits = dtypes.promote_half(logits)
    labels = dtypes.promote_half(labels)
    if kind == "softmax":
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -labels * logp
    else:  # sigmoid + binary xent
        per = (jnp.maximum(logits, 0) - logits * labels
               + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    if mask is not None:
        per = per * mask
    return jnp.sum(per, axis=tuple(range(1, per.ndim)))


@register_layer
@dataclasses.dataclass
class OutputLayer(FeedForwardLayer):
    """Dense + activation + loss (nn/conf/layers/OutputLayer.java)."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        p = {"W": self._sample_w(key, (self.n_in, self.n_out),
                                 self.n_in, self.n_out)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init,
                              dtypes.policy().param_dtype)
        return p, {}

    def _pre_output(self, params, x, *, training, rng):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        if x.ndim > 2 and not isinstance(self, RnnOutputLayer):
            x = x.reshape(x.shape[0], -1)
        if x.dtype == params["W"].dtype and x.dtype in (
                jnp.bfloat16, jnp.float16):
            # a half-precision head (parameters AND activations):
            # the logits leave the product in float32, not rounded
            # to half and promoted afterwards
            z = dtypes.einsum_f32("...d,dv->...v", x, params["W"])
        else:
            z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        # predictions/softmax never in half precision — under the
        # bf16 policy only HIDDEN activations ride bfloat16
        z = dtypes.promote_half(
            self._pre_output(params, x, training=training, rng=rng))
        return self.activation_fn()(z), state

    def has_loss(self) -> bool:
        return True

    def _fused_kind(self):
        a, l = self.activation.lower(), self.loss.lower()
        if a == "softmax" and l in ("mcxent", "negativeloglikelihood"):
            return "softmax"
        if a == "sigmoid" and l == "xent":
            return "sigmoid"
        return None

    def loss_from_input(self, params, x, labels, *, training, rng, mask=None):
        """Mean per-example score given the layer *input* (pre-dense)."""
        z = self._pre_output(params, x, training=training, rng=rng)
        kind = self._fused_kind()
        if kind is not None:
            per_ex = _stable_ce(z, labels, mask, kind)
        else:
            preds = self.activation_fn()(dtypes.promote_half(z))
            per_ex = losses_mod.get(self.loss)(labels, preds, mask)
        return jnp.mean(per_ex)


@register_layer
@dataclasses.dataclass
class RnnOutputLayer(OutputLayer):
    """Time-distributed output layer (nn/conf/layers/RnnOutputLayer.java).
    Input (B,T,F) → (B,T,n_out); loss masked per timestep. DL4J reshapes
    to 2-d ((B*T),F) internally (FeedForwardToRnnPreProcessor) — here the
    matmul is applied directly on the 3-d array. ``logits_divisor``
    divides the pre-activations (the loss sees them divided too)."""

    logits_divisor: float = 1.0

    # per-timestep logits; local-chunk mean loss pmeans to the global
    # mean under uniform shards (the wrapper enforces divisibility)
    seq_parallelizable = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def _pre_output(self, params, x, *, training, rng):
        z = super()._pre_output(params, x, training=training, rng=rng)
        return z if self.logits_divisor == 1.0 else z / self.logits_divisor

    def loss_from_input(self, params, x, labels, *, training, rng, mask=None):
        z = self._pre_output(params, x, training=training, rng=rng)
        # mask: (B,T) → broadcast over features
        m = mask[..., None] if (mask is not None and mask.ndim == 2) else mask
        kind = self._fused_kind()
        if kind is not None:
            per = _stable_ce(z, labels, m, kind)      # (B,) summed over T,F
        else:
            preds = self.activation_fn()(dtypes.promote_half(z))
            per = losses_mod.get(self.loss)(labels, preds, m)
        if mask is not None:
            from deeplearning4j_tpu.parallel.seq_context import (
                current_loss_axes)
            axes = current_loss_axes()
            if axes:
                # sequence-parallel trace: the masked mean's
                # denominator is GLOBAL (shards hold different
                # unmasked-step counts). Scale by the shard count so
                # the wrapper's mean-of-local-losses equals
                # Σ per / Σ mask over the whole batch.
                import jax
                total = jax.lax.psum(jnp.sum(mask), axes)
                n_sh = 1
                for a in axes:
                    n_sh *= jax.lax.psum(1, a)
                return jnp.sum(per) * n_sh / jnp.maximum(total, 1.0)
            # DL4J averages over *present* timesteps across the batch
            denom = jnp.maximum(jnp.sum(mask), 1.0)
            return jnp.sum(per) / denom
        return jnp.mean(per) / z.shape[1]


@register_layer
@dataclasses.dataclass
class LossLayer(OutputLayer):
    """Loss without weights (nn/conf/layers/LossLayer.java): input passes
    through activation straight to the loss."""

    def set_n_in(self, input_type: InputType) -> None:
        # weightless: n_out is the input width, never user-required
        # (the base class refuses a missing n_out)
        if self.n_in is None:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        self.n_out = self.n_in
        return {}, {}

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _pre_output(self, params, x, *, training, rng):
        return self.apply_input_dropout(x, training=training, rng=rng)


@register_layer
@dataclasses.dataclass
class CenterLossOutputLayer(OutputLayer):
    """Softmax + center loss (nn/conf/layers/CenterLossOutputLayer.java,
    impl nn/layers/training/CenterLossOutputLayer.java). Per-class
    feature centers live in *state* and are EMA-updated at train time
    (alpha), with the center-loss term weighted by lambda."""

    alpha: float = 0.05
    lambda_: float = 2e-4

    def initialize(self, key, input_type: InputType):
        params, _ = super().initialize(key, input_type)
        centers = jnp.zeros((self.n_out, self.n_in),
                            dtypes.policy().param_dtype)
        return params, {"centers": centers}

    def center_loss(self, state, x, labels):
        # x: (B, n_in) features; labels one-hot (B, n_out): squared
        # distances must not inherit bf16 activation precision
        x = dtypes.promote_half(x)
        labels = dtypes.promote_half(labels)
        assigned = labels @ state["centers"]           # (B, n_in)
        return 0.5 * jnp.mean(jnp.sum((x - assigned) ** 2, axis=-1))

    def update_centers(self, state, x, labels):
        counts = jnp.sum(labels, axis=0)[:, None]       # (n_out,1)
        sums = labels.T @ x                             # (n_out, n_in)
        mean_per_class = sums / jnp.maximum(counts, 1.0)
        has = (counts > 0)
        new = jnp.where(
            has, (1 - self.alpha) * state["centers"]
            + self.alpha * mean_per_class, state["centers"])
        return {**state, "centers": new}

    def loss_from_input(self, params, x, labels, *, training, rng, mask=None):
        base = super().loss_from_input(params, x, labels, training=training,
                                       rng=rng, mask=mask)
        return base  # center term added by the executor (needs state)
