"""NeuralNetConfiguration builder — the config DSL entry point.

Mirrors nn/conf/NeuralNetConfiguration.java's fluent Builder +
ListBuilder (:225-278): global defaults (seed, updater, weight init,
activation, regularization, dropout) that are stamped onto each layer
unless the layer overrides them, then ``.list()...build()`` →
:class:`MultiLayerConfiguration` or ``.graph_builder()`` →
:class:`ComputationGraphConfiguration`.

Python-idiomatic usage keeps the reference's shape::

    conf = (NeuralNetConfiguration.builder()
            .seed(12345)
            .updater(updaters.adam(1e-3))
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.nn.conf import updaters as updaters_mod
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, Layer

__all__ = ["NeuralNetConfiguration"]


_DEFAULTABLE_FIELDS = ("activation", "weight_init", "l1", "l2", "l1_bias",
                       "l2_bias", "updater", "gradient_normalization",
                       "gradient_normalization_threshold")


class NeuralNetConfiguration:
    """Global training/config defaults (one per network)."""

    def __init__(self):
        self.seed: int = 0
        self.updater_cfg: Optional[dict] = None
        self.defaults: Dict[str, Any] = {}
        self.dropout: float = 0.0
        self.mini_batch: bool = True
        self.max_num_line_search_iterations: int = 5
        self.optimization_algo: str = "stochastic_gradient_descent"
        self.gradient_clip: Optional[dict] = None   # {"type": "norm"|"value"|
                                                    #  "norm_per_param", "v":x}
        self.tbptt: Optional[dict] = None   # {"fwd_length": n, "bwd_length": n}
        # what a training step keeps of its forward pass for the
        # backward pass: everything (None), or each layer's input
        # alone, the layer computed again ("layers")
        self.recompute: Optional[str] = None

    # ---- fluent builder (mirrors Builder method names, snake_cased) ----
    @staticmethod
    def builder() -> "NeuralNetConfiguration":
        return NeuralNetConfiguration()

    def seed_(self, s: int):
        self.seed = int(s)
        return self

    # keep java-style name too
    def set_seed(self, s: int):
        return self.seed_(s)

    def updater(self, cfg: dict):
        self.updater_cfg = cfg
        return self

    def learning_rate(self, lr: float):
        if self.updater_cfg is None:
            self.updater_cfg = updaters_mod.sgd(lr)
        else:
            self.updater_cfg = {**self.updater_cfg, "lr": lr}
        return self

    def weight_init(self, scheme: str, distribution: Optional[dict] = None):
        self.defaults["weight_init"] = scheme
        if distribution is not None:
            self.defaults["weight_distribution"] = distribution
        return self

    def activation(self, a: str):
        self.defaults["activation"] = a
        return self

    def l1(self, v: float):
        self.defaults["l1"] = v
        return self

    def l2(self, v: float):
        self.defaults["l2"] = v
        return self

    def drop_out(self, drop_prob: float):
        self.dropout = drop_prob
        return self

    def gradient_normalization(self, kind: str, threshold: float = 1.0):
        """kind ∈ {'clip_l2_per_layer','clip_element_wise',
        'renormalize_l2_per_layer','clip_l2_per_param_type'} — mirrors
        GradientNormalization enum."""
        self.defaults["gradient_normalization"] = kind
        self.defaults["gradient_normalization_threshold"] = threshold
        return self

    def clip_gradient_norm(self, v: float):
        self.gradient_clip = {"type": "norm", "v": v}
        return self

    def clip_gradient_value(self, v: float):
        self.gradient_clip = {"type": "value", "v": v}
        return self

    def optimization_algorithm(self, algo: str):
        self.optimization_algo = algo
        return self

    def backprop_type(self, kind: str, fwd_length: int = 20,
                      bwd_length: int = 20):
        if kind.lower() in ("truncatedbptt", "tbptt", "truncated_bptt"):
            self.tbptt = {"fwd_length": fwd_length, "bwd_length": bwd_length}
        return self

    def recompute_layers(self, on: bool = True):
        """A training step of ``MultiLayerNetwork.fit`` keeps each
        layer's input and, where the layer's ``apply`` went through
        the flash kernels, that call's output and row statistics
        (``ops.attention.FLASH_KEPT``: 0.68 GB over the five layers of
        ``trinity_train_8k``, 134 MB of ``o`` and 1 MB of ``lse``
        each), and computes the rest of the layer again in the
        backward pass (``jax.checkpoint`` around every layer's
        ``apply``): activation memory of one layer at a time, about a
        third more arithmetic, and the forward kernel once a step. Off,
        the step is what it was."""
        self.recompute = "layers" if on else None
        return self

    # ---- terminals ----
    def list(self) -> "ListBuilder":
        return ListBuilder(self)

    def graph_builder(self):
        from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
        return GraphBuilder(self)

    def stamp_defaults(self, layer: Layer) -> Layer:
        """Apply global defaults to fields the layer left at their
        dataclass defaults (reference: Builder.layer(...) copies global
        conf into each NeuralNetConfiguration clone)."""
        if isinstance(layer, BaseLayer):
            field_defaults = {f.name: f.default
                              for f in dataclasses.fields(type(layer))}
            base_defaults = {f.name: f.default
                             for f in dataclasses.fields(BaseLayer)}
            for k, v in self.defaults.items():
                # stamp only fields the user left at the default AND whose
                # subclass didn't deliberately customize the default (e.g.
                # OutputLayer.activation = softmax stays softmax)
                if (k in field_defaults
                        and getattr(layer, k) == field_defaults[k]
                        and field_defaults[k] == base_defaults.get(
                            k, field_defaults[k])):
                    setattr(layer, k, v)
            if layer.updater is None and self.updater_cfg is not None:
                # leave None → falls back to global updater at train time
                pass
        if self.dropout and layer.dropout == 0.0:
            layer.dropout = self.dropout
        return layer

    def global_to_dict(self) -> dict:
        d = {
            "seed": self.seed,
            "updater": self.updater_cfg,
            "defaults": self.defaults,
            "dropout": self.dropout,
            "optimization_algo": self.optimization_algo,
            "gradient_clip": self.gradient_clip,
            "tbptt": self.tbptt,
        }
        if self.recompute is not None:      # absent: as it was written
            d["recompute"] = self.recompute
        return d

    @staticmethod
    def global_from_dict(d: dict) -> "NeuralNetConfiguration":
        c = NeuralNetConfiguration()
        c.seed = d.get("seed", 0)
        c.updater_cfg = d.get("updater")
        c.defaults = d.get("defaults", {}) or {}
        c.dropout = d.get("dropout", 0.0)
        c.optimization_algo = d.get("optimization_algo",
                                    "stochastic_gradient_descent")
        c.gradient_clip = d.get("gradient_clip")
        c.tbptt = d.get("tbptt")
        c.recompute = d.get("recompute")
        if c.recompute not in (None, "layers"):
            raise ValueError(f"recompute {c.recompute!r}: None or "
                             "'layers'")
        return c


class ListBuilder:
    """NeuralNetConfiguration.ListBuilder (:225): ordered layer stack →
    MultiLayerConfiguration."""

    def __init__(self, conf: NeuralNetConfiguration):
        self._conf = conf
        self._layers: List[Layer] = []
        self._input_type: Optional[InputType] = None

    def layer(self, layer: Layer, index: Optional[int] = None):
        layer = self._conf.stamp_defaults(layer)
        if index is None:
            self._layers.append(layer)
        else:
            while len(self._layers) <= index:
                self._layers.append(None)
            self._layers[index] = layer
        return self

    def set_input_type(self, t: InputType):
        self._input_type = t
        return self

    def build(self):
        from deeplearning4j_tpu.nn.conf.multi_layer import (
            MultiLayerConfiguration)
        if any(l is None for l in self._layers):
            raise ValueError("Gap in layer indices")
        return MultiLayerConfiguration(self._conf, list(self._layers),
                                       self._input_type)
