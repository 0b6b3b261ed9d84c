"""Sparse experts.

``SparseExpertsLayer`` is the routed feed-forward of the DeepSeek-V2 /
V3 line (arXiv 2405.04434 §2.2, 2412.19437 §2.1.2): a router scores
every token against ALL ``n_routed_experts`` experts, the ``top_k``
best are selected, and the token's output is the weighted sum of the
selected experts' SiLU-gated MLPs plus a shared expert that every
token passes through.

The layer is told which experts it HOLDS (``held = (first, count)``):
one chip's share of an expert-parallel group. The router keeps its
full width and the normaliser runs over all selected experts, held or
not; the layer computes the part of the sum that its own experts
give. On one chip it runs without its exchange: what absent experts
would add is left out, and nothing stands in for them. Summed over
the shares of a group, with the shared expert counted once, the parts
give the whole layer (tests/test_latent_moe.py holds that).

With ``scoring_func="softmax"``, ``router_bias`` and
``n_zero_experts`` it is LongCat-Flash's router (arXiv 2509.01322
§2.1): a softmax over the routed AND the zero-compute experts, a
correction bias that enters the selection only, and identity experts
whose pick returns the token itself times its weight. The zero
experts' part needs no exchange, so every share computes it.

The decoder blocks that carry this layer are in
``decoder_blocks.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import _mm
from deeplearning4j_tpu.ops import grouped_experts

__all__ = ["SparseExpertsLayer", "swiglu"]

_F32 = jnp.float32


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x W_gate) * x W_up) W_down``; float32 accumulation and
    activation, operands and result in ``x``'s dtype."""
    g = einsum_f32("...d,dw->...w", x, w_gate)
    u = einsum_f32("...d,dw->...w", x, w_up)
    return _mm((jax.nn.silu(g) * u).astype(x.dtype), w_down)


@register_layer
@dataclasses.dataclass
class SparseExpertsLayer(BaseLayer):
    """Routed + shared experts, (B,T,C) -> (B,T,C)."""

    n_in: Optional[int] = None
    n_routed_experts: int = 16          # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 4
    expert_width: int = 32
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"       # or "softmax", over the width
    # identity experts behind the routed ones in the router's width
    # (LongCat-Flash's zero-compute experts): a pick of one returns
    # the token itself times its weight
    n_zero_experts: int = 0
    # a correction bias ``br`` over the router's width that enters
    # the SELECTION and not the weights
    router_bias: bool = False

    seq_parallelizable = True           # per token

    def __post_init__(self):
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(
                f"scoring_func {self.scoring_func!r}: 'sigmoid' or "
                "'softmax'")
        if self.held is not None:
            self.held = (int(self.held[0]), int(self.held[1]))
        first, count = self.held_range()
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"held {self.held} lies outside the router's "
                f"{self.n_routed_experts} experts")

    def held_range(self):
        return self.held or (0, self.n_routed_experts)

    @property
    def held_experts(self) -> int:
        return self.held_range()[1]

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.n_zero_experts

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        d, w = self.n_in, self.expert_width
        n = self.held_range()[1]
        ks = jax.random.split(key, 7)
        p = {"Wr": self._sample_w(ks[0], (d, self.router_width), d,
                                  self.router_width),
             "Wg": self._sample_w(ks[1], (n, d, w), d, w),
             "Wu": self._sample_w(ks[2], (n, d, w), d, w),
             "Wd": self._sample_w(ks[3], (n, w, d), w, d)}
        if self.n_shared_experts:
            ws = w * self.n_shared_experts
            p.update(Wsg=self._sample_w(ks[4], (d, ws), d, ws),
                     Wsu=self._sample_w(ks[5], (d, ws), d, ws),
                     Wsd=self._sample_w(ks[6], (ws, d), ws, d))
        if self.router_bias:
            p["br"] = jnp.zeros((self.router_width,),
                                dtypes.policy().param_dtype)
        return p, {}

    # ---- the router ----
    def route(self, params, x):
        """``x`` (N,C) -> (ids (N,k) int32, weights (N,k) float32):
        the selected experts of every token over the router's whole
        width, and their combine weights."""
        with jax.named_scope("moe/router"):
            scores = einsum_f32("nd,de->ne", x, params["Wr"])
            scores = (jax.nn.sigmoid(scores)
                      if self.scoring_func == "sigmoid"
                      else jax.nn.softmax(scores, axis=-1))
            if self.router_bias:
                _, ids = jax.lax.top_k(
                    scores + params["br"].astype(_F32), self.top_k)
                w = jnp.take_along_axis(scores, ids, axis=-1)
            else:
                w, ids = jax.lax.top_k(scores, self.top_k)
            if self.norm_topk_prob:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return ids.astype(jnp.int32), w * self.routed_scaling_factor

    # ---- the held experts' part ----
    def takes_grouped_pass(self, rows: int, dtype) -> bool:
        """Does a serving step of ``rows`` rows run the grouped pass
        (``ops.grouped_experts.grouped_pass`` of this layer's
        shapes)?"""
        return grouped_experts.grouped_pass(
            rows, self.top_k, self.router_width, self.n_in,
            self.expert_width, dtype)

    def carries_rows(self, rows: int, dtype) -> bool:
        """Does a serving step of ``rows`` rows carry them on the
        weights the held experts' pass reads anyway: the grouped pass,
        at a row count where the kernel's time is still its weights'
        (``ops.grouped_experts.weight_bound``)?"""
        return (self.takes_grouped_pass(rows, dtype)
                and grouped_experts.weight_bound(
                    rows, self.n_in, self.expert_width))

    def apply_counted(self, params, x, active=None, stream=False):
        """(out, counts): ``counts`` (held,) int32, how many tokens
        each held expert served. ``active`` marks the rows that carry
        a token, (B,) for whole sequences or (B,T) row by row (the
        chunk program's ragged rows); the others reach no expert and
        are not counted (a free slot of a decode batch). ``stream``:
        the call is a serving step's, see ``apply_tallied``."""
        out, tally = self.apply_tallied(params, x, active, stream)
        return out, tally["held"]

    def apply_tallied(self, params, x, active=None, stream=False):
        """(out, tally): ``apply_counted`` with all three counts of
        the ``active`` rows, ``{"held": (held,) tokens a held expert,
        "zero": () (row, zero expert) pairs, "selected": () (row,
        selected expert) pairs}``, int32. The held experts' part has
        three forms that differ by the order of a float32 sum: the
        DENSE pass, every row through every held expert; in a
        serving step (``stream``: nothing differentiates it) whose
        shapes ``takes_grouped_pass`` admits, the GROUPED pass, a
        kernel over the selected pairs alone; and off a serving step
        past an MXU tile of rows (``pairs_pass``) the PAIRS pass,
        sorted pairs through grouped matrix products, which
        differentiates and drops no pair at any skew
        (``ops.grouped_experts``)."""
        shape = x.shape
        x = x.reshape(-1, shape[-1]).astype(params["Wr"].dtype)
        ids, w = self.route(params, x)
        first, count = self.held_range()
        rows = None
        with jax.named_scope("moe/experts"):
            # combine weight of every (token, held expert): 0 unless
            # selected
            hit = (ids - first)[:, :, None] == jnp.arange(count)
            if active is not None:                       # (N,k,held)
                rows = jnp.repeat(active.reshape(-1),
                                  x.shape[0] // active.size)
                hit = hit & rows[:, None, None]
            comb = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
            counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
            if stream and self.takes_grouped_pass(x.shape[0], x.dtype):
                out = grouped_experts.pallas_grouped_experts(
                    x, jnp.any(hit, axis=1), comb, params["Wg"],
                    params["Wu"], params["Wd"])
            elif not stream and grouped_experts.pairs_pass(x.shape[0]):
                out = grouped_experts.pairs_experts(
                    x, jnp.where(jnp.any(hit, axis=2), ids - first,
                                 count), w, params["Wg"], params["Wu"],
                    params["Wd"])
            else:
                # every token goes through every held expert and the
                # weight picks: up to an MXU tile of rows the
                # experts' weights, not the rows, bound the time
                # (PERF.md)
                g = einsum_f32("nd,edw->enw", x, params["Wg"])
                u = einsum_f32("nd,edw->enw", x, params["Wu"])
                y = einsum_f32("enw,ewd->end",
                               (jax.nn.silu(g) * u).astype(x.dtype),
                               params["Wd"])
                out = jnp.einsum("end,ne->nd", y, comb)
        if self.n_shared_experts:
            with jax.named_scope("moe/shared"):
                out = out + swiglu(x, params["Wsg"], params["Wsu"],
                                   params["Wsd"]).astype(_F32)
        n_zero = jnp.zeros((), jnp.int32)
        if self.n_zero_experts:
            # on the token's own chip in a deployment: no exchange,
            # so every share computes it for every row it has
            with jax.named_scope("moe/zero"):
                zero = ids >= self.n_routed_experts      # (N,k)
                if rows is not None:
                    zero = zero & rows[:, None]
                out = out + (jnp.sum(jnp.where(zero, w, 0.0), axis=1)
                             [:, None] * x.astype(_F32))
                n_zero = jnp.sum(zero, dtype=jnp.int32)
        n_rows = x.shape[0] if rows is None else jnp.sum(
            rows, dtype=jnp.int32)
        tally = {"held": counts, "zero": n_zero,
                 "selected": jnp.asarray(n_rows * self.top_k,
                                         jnp.int32)}
        return out.astype(x.dtype).reshape(shape), tally

    def apply_with_counts(self, params, state, x, *, training=False,
                          rng=None, mask=None):
        """``apply`` with the held counts of the call as a third
        value: what a train step asks a layer with experts for
        (``MultiLayerNetwork._apply_in_train_step``)."""
        x = self.apply_input_dropout(x, training=training, rng=rng)
        out, counts = self.apply_counted(params, x)
        return out, state, counts

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        return self.apply_with_counts(params, state, x,
                                      training=training, rng=rng)[:2]
