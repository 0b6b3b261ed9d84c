"""Core feed-forward layers: Dense, Activation, Dropout, Embedding,
AutoEncoder.

Reference: nn/conf/layers/DenseLayer.java + nn/layers/feedforward/**.
Dense on an RNN input applies time-distributed (the reference routes
through an RnnToFeedForwardPreProcessor; here a 3-d input just works —
the matmul contracts the last axis).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    FeedForwardLayer, BaseLayer, Layer, register_layer,
)

__all__ = ["DenseLayer", "ActivationLayer", "DropoutLayer",
           "EmbeddingLayer", "EmbeddingSequenceLayer", "AutoEncoder",
           "RBM"]


@register_layer
@dataclasses.dataclass
class DenseLayer(FeedForwardLayer):
    """Fully connected layer (reference nn/conf/layers/DenseLayer.java,
    impl nn/layers/feedforward/dense/DenseLayer.java)."""

    # on (B,T,C) recurrent input the matmul is per-timestep
    seq_parallelizable = True

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        p = {"W": self._sample_w(key, (self.n_in, self.n_out),
                                 self.n_in, self.n_out)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init,
                              dtypes.policy().param_dtype)
        return p, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        if x.ndim > 2 and x.shape[-1] != params["W"].shape[0]:
            x = x.reshape(x.shape[0], -1)   # cnn -> flatten
        # MXU-native compute dtype (no-op casts under the f32 default)
        pol = dtypes.policy()
        y = pol.cast_to_output(
            pol.cast_to_compute(x) @ pol.cast_to_compute(params["W"]))
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state

    def output_type(self, input_type: InputType) -> InputType:
        if self.n_out is None:
            raise ValueError("DenseLayer requires n_out")
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)


@register_layer
@dataclasses.dataclass
class ActivationLayer(BaseLayer):
    """Activation-only layer (nn/conf/layers/ActivationLayer.java)."""

    seq_parallelizable = True          # elementwise

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return self.activation_fn()(x), state


@register_layer
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout (nn/conf/layers/DropoutLayer.java). Identity at
    inference; inverted-dropout scaling at train time."""

    seq_parallelizable = True          # elementwise

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return self.apply_input_dropout(x, training=training, rng=rng), state


@register_layer
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index → vector lookup (nn/conf/layers/EmbeddingLayer.java, impl
    nn/layers/feedforward/embedding/EmbeddingLayer.java). Input: int ids
    of shape (B,) or (B,1); a one-hot-equivalent gather — MXU-friendly
    when XLA lowers to take()."""

    def initialize(self, key, input_type: InputType):
        if self.n_in is None:
            self.n_in = input_type.flat_size()
        p = {"W": self._sample_w(key, (self.n_in, self.n_out),
                                 self.n_in, self.n_out)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init,
                              dtypes.policy().param_dtype)
        return p, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        y = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)


@register_layer
@dataclasses.dataclass
class EmbeddingSequenceLayer(FeedForwardLayer):
    """Sequence of ids (B,T) → (B,T,n_out) (reference added this in
    later versions; capability parity with Keras Embedding import).
    ``multiplier`` scales the rows that come out (in float32, rounded
    once)."""

    multiplier: float = 1.0

    seq_parallelizable = True          # per-token gather

    def initialize(self, key, input_type: InputType):
        if self.n_in is None:
            self.n_in = input_type.size
        return {"W": self._sample_w(key, (self.n_in, self.n_out),
                                    self.n_in, self.n_out)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = jnp.take(params["W"], idx, axis=0)
        if self.multiplier != 1.0:
            y = (y.astype(jnp.float32) * self.multiplier).astype(y.dtype)
        return y, state

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)


@register_layer
@dataclasses.dataclass
class RBM(FeedForwardLayer):
    """Restricted Boltzmann Machine (nn/conf/layers/RBM.java, impl
    nn/layers/feedforward/rbm/RBM.java — the reference's legacy
    pretraining layer).

    Supervised forward = hidden activations (sigmoid propup), like the
    reference. Unsupervised pretraining uses contrastive divergence:
    ``pretrain_loss`` is the free-energy difference F(v) − F(ṽ) with
    the CD-1 reconstruction ṽ held constant (stop_gradient), whose
    gradient is exactly the CD-1 update — so the same jitted
    pretraining machinery (jax.grad + optax) that serves AutoEncoder/VAE
    drives RBM, instead of the reference's hand-coded Gibbs updates.
    """

    k: int = 1                      # CD-k Gibbs steps
    activation: str = "sigmoid"
    visible_unit: str = "binary"    # 'binary' | 'gaussian'
    hidden_unit: str = "binary"

    def __post_init__(self):
        # the softplus free-energy form assumes sigmoid-binary hiddens;
        # reject configs that would silently train a different model
        if self.activation != "sigmoid":
            raise ValueError("RBM supports only sigmoid hidden "
                             "activation (free-energy objective)")
        if self.visible_unit not in ("binary", "gaussian"):
            raise ValueError(f"RBM visible_unit must be 'binary' or "
                             f"'gaussian', got '{self.visible_unit}'")
        # the softplus marginalization below is the BINARY-hidden free
        # energy; gaussian hiddens need a quadratic term we don't
        # implement — reject rather than silently fit the wrong model
        if self.hidden_unit != "binary":
            raise ValueError(f"RBM hidden_unit supports only 'binary', "
                             f"got '{self.hidden_unit}'")

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        pd = dtypes.policy().param_dtype
        return {
            "W": self._sample_w(key, (self.n_in, self.n_out),
                                self.n_in, self.n_out),
            "b": jnp.full((self.n_out,), self.bias_init, pd),  # hidden
            "vb": jnp.zeros((self.n_in,), pd),                 # visible
        }, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        return jax.nn.sigmoid(x @ params["W"] + params["b"]), state

    def _free_energy(self, params, v):
        # F(v) = -v·vb - Σ softplus(vW + hb)
        vis = jnp.sum(v * params["vb"], axis=-1)
        hid = jnp.sum(jax.nn.softplus(v @ params["W"] + params["b"]),
                      axis=-1)
        return -vis - hid

    def _gibbs(self, params, v, rng):
        ph = jax.nn.sigmoid(v @ params["W"] + params["b"])
        k1, _ = jax.random.split(rng)
        h = jax.random.bernoulli(k1, ph).astype(v.dtype)
        pv = h @ params["W"].T + params["vb"]
        if self.visible_unit == "binary":
            pv = jax.nn.sigmoid(pv)
        return pv

    def pretrain_loss(self, params, x, rng):
        v_model = x
        keys = jax.random.split(rng, max(self.k, 1))
        for kk in keys:
            v_model = self._gibbs(params, v_model, kk)
        v_model = jax.lax.stop_gradient(v_model)
        return jnp.mean(self._free_energy(params, x)
                        - self._free_energy(params, v_model))

    def reconstruction_error(self, params, x, rng):
        recon = self._gibbs(params, x, rng)
        return jnp.mean((x - recon) ** 2)


@register_layer
@dataclasses.dataclass
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder layer (nn/conf/layers/AutoEncoder.java,
    impl nn/layers/feedforward/autoencoder/AutoEncoder.java).

    Supervised forward = encode only. Unsupervised pretraining
    (corrupt → encode → decode → reconstruction loss) is exposed via
    ``pretrain_loss`` and driven by MultiLayerNetwork.pretrain, the
    analog of BasePretrainNetwork.
    """

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        k1, k2 = jax.random.split(key)
        pd = dtypes.policy().param_dtype
        return {
            "W": self._sample_w(k1, (self.n_in, self.n_out),
                                self.n_in, self.n_out),
            "b": jnp.full((self.n_out,), self.bias_init, pd),
            "vb": jnp.zeros((self.n_in,), pd),     # visible bias (decode)
        }, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        return self.activation_fn()(x @ params["W"] + params["b"]), state

    def pretrain_loss(self, params, x, rng):
        from deeplearning4j_tpu.nn import losses as losses_mod
        act = self.activation_fn()
        if self.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level,
                                        x.shape)
            xc = jnp.where(keep, x, 0.0)
        else:
            xc = x
        h = act(xc @ params["W"] + params["b"])
        recon = act(h @ params["W"].T + params["vb"])
        return jnp.mean(losses_mod.get(self.loss)(x, recon, None))


@register_layer
@dataclasses.dataclass
class RecursiveAutoEncoder(FeedForwardLayer):
    """Recursive autoencoder over sequences
    (nn/conf/layers/RecursiveAutoEncoder... — reference impl
    nn/layers/feedforward/recursive/RecursiveAutoEncoder.java): the
    hidden code folds the sequence left to right — at each step the
    carry and the next input are jointly encoded, and pretraining
    reconstructs the [carry; input] pair from the code. TPU-native
    shape: the fold is a ``lax.scan`` (sequential by definition; the
    matmuls inside still batch over B on the MXU).

    Supervised forward = the final code (B, n_out) — a
    sequence-collapsing encoder. ``pretrain_loss`` = mean
    reconstruction error across steps, driven by
    MultiLayerNetwork.pretrain like the other BasePretrainNetwork
    analogs (AutoEncoder/RBM/VAE).
    """

    loss: str = "mse"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def set_n_in(self, input_type: InputType) -> None:
        self.n_in = input_type.size

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        k1, k2 = jax.random.split(key)
        pd = dtypes.policy().param_dtype
        z = self.n_out + self.n_in          # [carry; x_t]
        return {
            "W": self._sample_w(k1, (z, self.n_out), z, self.n_out),
            "b": jnp.full((self.n_out,), self.bias_init, pd),
            "Wd": self._sample_w(k2, (self.n_out, z), self.n_out, z),
            "vb": jnp.zeros((z,), pd),       # decode bias
        }, {}

    def _fold(self, params, x, mask=None):
        """x: (B, T, C) → (final code (B, n_out), mean recon loss).
        ``mask`` (B, T) 0/1: padded steps neither advance the carry
        nor contribute reconstruction loss (same state-gating contract
        as the recurrent layers)."""
        from deeplearning4j_tpu.nn import losses as losses_mod
        act = self.activation_fn()
        loss_fn = losses_mod.get(self.loss)
        B = x.shape[0]
        h0 = jnp.zeros((B, self.n_out), x.dtype)
        if mask is None:
            m_t = jnp.ones((x.shape[1], B), x.dtype)
        else:
            m_t = jnp.swapaxes(jnp.asarray(mask, x.dtype), 0, 1)

        def step(h, inp):
            xt, mt = inp
            z = jnp.concatenate([h, xt], axis=-1)
            code = act(z @ params["W"] + params["b"])
            recon = act(code @ params["Wd"] + params["vb"])
            h_new = jnp.where(mt[:, None] > 0, code, h)
            per_ex = jnp.mean(loss_fn(z, recon, None).reshape(B, -1),
                              axis=-1)
            return h_new, (jnp.sum(per_ex * mt), jnp.sum(mt))

        h, (lsum, msum) = jax.lax.scan(step, h0,
                                       (jnp.swapaxes(x, 0, 1), m_t))
        mean_loss = jnp.sum(lsum) / jnp.maximum(jnp.sum(msum), 1.0)
        return h, mean_loss

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        h, _ = self._fold(params, x, mask)
        return h, state

    def pretrain_loss(self, params, x, rng, mask=None):
        _, mean_loss = self._fold(params, x, mask)
        return mean_loss
