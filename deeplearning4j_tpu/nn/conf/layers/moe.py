"""Sparse experts, and the decoder block that carries them.

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

``LatentDecoderBlock`` is the pre-RMSNorm residual block
``h = x + MLA(norm(x)); y = h + F(norm(h))`` with ``F`` either a
dense SiLU-gated MLP or the expert layer.

``ShortcutExpertBlock`` is LongCat-Flash's shortcut-connected layer
(§2.2): two latent attentions and two dense MLPs in sequence, and one
expert layer that reads the first sub-layer's normed hidden state and
joins the residual stream at the end of the second.

``GroupedQueryDecoderBlock`` is ``LatentDecoderBlock``'s residual
block over grouped-query attention (global, or a sliding window with
a learned sink) and a sigmoid router with a correction bias and no
shared expert: MiMo-V2's layer.

``StateSpaceDecoderBlock`` is the same residual block over a Mamba-2
mixer (``state_space.py``) and the dense MLP. It and
``GroupedQueryDecoderBlock`` take a ``residual_multiplier`` on both
branches: together they are Granite-4.0-H's two kinds of layer.

``ShortConvDecoderBlock`` is that block over a gated short
convolution (``short_conv.py``) and the dense MLP or
``GroupedQueryDecoderBlock``'s expert layer: with that block and its
``qk_norm`` it is LFM2's two kinds of layer. Both blocks over a mixer
whose cache is a row a slot are one ``_SlotStateBlock``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    GroupedQueryAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    LatentAttentionLayer, _mm)
from deeplearning4j_tpu.nn.conf.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.conf.layers.short_conv import (
    ShortConvMixerLayer)
from deeplearning4j_tpu.nn.conf.layers.state_space import Mamba2MixerLayer
from deeplearning4j_tpu.ops import grouped_experts

__all__ = ["SparseExpertsLayer", "LatentDecoderBlock",
           "ShortcutExpertBlock", "GroupedQueryDecoderBlock",
           "StateSpaceDecoderBlock", "ShortConvDecoderBlock", "swiglu"]

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
        two forms that differ by the order of a float32 sum: the
        DENSE pass, every row through every held expert, and, in a
        serving step (``stream``: nothing differentiates it) whose
        shapes ``takes_grouped_pass`` admits, the GROUPED pass over
        the selected pairs alone (``ops.grouped_experts``)."""
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

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        x = self.apply_input_dropout(x, training=training, rng=rng)
        return self.apply_counted(params, x)[0], state


def _residual(h, f, multiplier=1.0):
    """``h + multiplier * f`` in ``h``'s dtype; a multiplier that is
    not 1 scales in float32, so the sum is rounded once."""
    if multiplier == 1.0:
        return h + f
    return (h.astype(_F32) + multiplier * f.astype(_F32)).astype(h.dtype)


def _ffn_half(params, h, moe, eps, active=None, multiplier=1.0,
              stream=False):
    """The second half of a pre-RMSNorm decoder block,
    ``(h + multiplier * F(norm(h)), counts or None)``: ``F`` is the
    expert layer ``moe`` (parameters ``params["moe"]``) or, where that
    is None, the dense SiLU-gated MLP ``Wg, Wu, Wd``. ``stream``: a
    serving step's call (``SparseExpertsLayer.apply_tallied``)."""
    z = rms_norm(h, params["norm2_gain"], eps)
    if moe is None:
        with jax.named_scope("mlp"):
            return _residual(h, swiglu(z, params["Wg"], params["Wu"],
                                       params["Wd"]), multiplier), None
    f, counts = moe.apply_counted(params["moe"], z, active, stream)
    return _residual(h, f, multiplier), counts


class _ExpertsPart:
    """What a block whose ``_ensure_parts()[1]`` is the expert layer,
    or None where it carries the dense MLP, says of that layer."""

    def experts_grouped(self, rows: int, dtype) -> bool:
        """Does a serving step of ``rows`` rows run this block's
        experts as the grouped pass? The paged session asks, for
        ``serving_moe_grouped_steps_total``."""
        moe = self._ensure_parts()[1]
        return moe is not None and moe.takes_grouped_pass(rows, dtype)


def _init_decoder_block(block, key, attn, moe, mixer="attn"):
    """Parameters of a pre-RMSNorm decoder block over ``attn`` (the
    sequence mixer, whose parameters go under the key ``mixer``) and
    ``moe`` (None: the dense MLP of ``block.intermediate_size``)."""
    ka, km, k1, k2, k3 = jax.random.split(key, 5)
    d, ff = block.n_in, block.intermediate_size
    pd = dtypes.policy().param_dtype
    t = InputType.recurrent(d)
    p = {"norm1_gain": jnp.ones((d,), pd),
         "norm2_gain": jnp.ones((d,), pd),
         mixer: attn.initialize(ka, t)[0]}
    if moe is not None:
        p["moe"] = moe.initialize(km, t)[0]
    else:
        p.update(Wg=block._sample_w(k1, (d, ff), d, ff),
                 Wu=block._sample_w(k2, (d, ff), d, ff),
                 Wd=block._sample_w(k3, (ff, d), ff, d))
    return p, {}


def _biased_sigmoid_experts(block, held, common):
    """The expert layer of ``block``'s flat fields over the ``held``
    share, or None where it has no routed experts: a sigmoid router
    with its selection-only correction bias, the selected weights
    normalised, no shared expert (MiMo-V2's and LFM2's)."""
    if not block.n_routed_experts:
        return None
    return SparseExpertsLayer(
        n_routed_experts=block.n_routed_experts, held=held,
        top_k=block.top_k, expert_width=block.expert_width,
        n_shared_experts=0,
        routed_scaling_factor=block.routed_scaling_factor,
        norm_topk_prob=True, scoring_func="sigmoid", router_bias=True,
        **common)


@register_layer
@dataclasses.dataclass
class LatentDecoderBlock(_ExpertsPart, BaseLayer):
    """Pre-RMSNorm decoder block: latent attention, then a dense
    SiLU-gated MLP (``n_routed_experts == 0``) or the expert layer.
    The fields are the two sub-layers' own, flat, so that the block
    round-trips through JSON like every DSL layer."""

    n_in: Optional[int] = None
    eps: float = 1e-6
    # latent attention (LatentAttentionLayer)
    n_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 4
    v_head_dim: int = 8
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    # dense MLP width (used when n_routed_experts == 0)
    intermediate_size: int = 128
    # expert layer (SparseExpertsLayer)
    n_routed_experts: int = 0
    held: Optional[Tuple[int, int]] = None
    top_k: int = 4
    expert_width: int = 32
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    @property
    def stream_aux(self) -> bool:
        """Does a decode step of this block return counts beside its
        output (``apply_stream_paged_aux``)? The paged session asks."""
        return self.n_routed_experts > 0

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = dict(n_in=self.n_in, weight_init=self.weight_init,
                          weight_distribution=self.weight_distribution)
            self._attn = LatentAttentionLayer(
                n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
                rope_scaling=self.rope_scaling, eps=self.eps, **common)
            self._moe = None
            if self.n_routed_experts:
                self._moe = SparseExpertsLayer(
                    n_routed_experts=self.n_routed_experts,
                    held=self.held, top_k=self.top_k,
                    expert_width=self.expert_width,
                    n_shared_experts=self.n_shared_experts,
                    routed_scaling_factor=self.routed_scaling_factor,
                    norm_topk_prob=self.norm_topk_prob, **common)
        return self._attn, self._moe

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        return _init_decoder_block(self, key, *self._ensure_parts())

    def _ffn_half(self, params, h, active=None, stream=False):
        return _ffn_half(params, h, self._ensure_parts()[1], self.eps,
                         active, stream=stream)

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        attn, _ = self._ensure_parts()
        x = x.astype(params["norm1_gain"].dtype)
        with jax.named_scope("mla"):
            a, _ = attn.apply(
                params["attn"], {},
                rms_norm(x, params["norm1_gain"], self.eps),
                training=training, rng=rng, mask=mask)
        return self._ffn_half(params, x + a)[0], state

    # ---- paged decode ----
    def zero_page_pool(self, n_pages: int, page_size: int, dtype):
        return self._ensure_parts()[0].zero_page_pool(
            n_pages, page_size, dtype)

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        return self._ensure_parts()[0].paged_reads_by_table(
            page_size, t, dtype)

    def apply_stream_paged_aux(self, params, pool, table, pos, x,
                               active=None, n_valid=None):
        """(out, pool, counts): one decode step through the block;
        ``counts`` is None for a dense block, else the (held,) tokens
        each held expert served among the ``active`` slots, or rows
        where the chunk program gives a (slots, t) mask beside its
        ``n_valid``."""
        attn, _ = self._ensure_parts()
        x = x.astype(params["norm1_gain"].dtype)
        with jax.named_scope("mla"):
            a, pool = attn.apply_stream_paged(
                params["attn"], pool, table, pos,
                rms_norm(x, params["norm1_gain"], self.eps), n_valid)
        h, counts = self._ffn_half(params, x + a, active, stream=True)
        return h, pool, counts

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        h, pool, _ = self.apply_stream_paged_aux(
            params, pool, table, pos, x, n_valid=n_valid)
        return h, pool


@register_layer
@dataclasses.dataclass
class ShortcutExpertBlock(_ExpertsPart, BaseLayer):
    """LongCat-Flash's shortcut-connected expert layer::

        h0 = x  + MLA_0(norm(x));   z0 = norm(h0)
        m  = MoE(z0)                    # the shortcut: read here ...
        h1 = h0 + MLP_0(z0)
        h2 = h1 + MLA_1(norm(h1))
        y  = h2 + MLP_1(norm(h2)) + m   # ... joined here

    Every norm has its own gain; the two attentions have their own
    weights and their own caches (``zero_page_pool`` gives
    ``{"a0", "a1"}`` over one page table). The fields are the parts'
    own, flat, as ``LatentDecoderBlock`` has them."""

    n_in: Optional[int] = None
    eps: float = 1e-5
    # the two latent attentions (LatentAttentionLayer)
    n_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 4
    v_head_dim: int = 8
    rope_theta: float = 10000.0
    scale_q_lora: bool = True
    scale_kv_lora: bool = True
    # the two dense MLPs
    intermediate_size: int = 128
    # the expert layer (SparseExpertsLayer): a softmax router with
    # its correction bias, no normaliser over the selected, no
    # shared expert
    n_routed_experts: int = 16
    n_zero_experts: int = 8
    held: Optional[Tuple[int, int]] = None
    top_k: int = 4
    expert_width: int = 32
    routed_scaling_factor: float = 1.0

    stream_aux = True       # a decode step returns the expert tally

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = dict(n_in=self.n_in, weight_init=self.weight_init,
                          weight_distribution=self.weight_distribution)
            self._attn = LatentAttentionLayer(
                n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
                eps=self.eps, scale_q_lora=self.scale_q_lora,
                scale_kv_lora=self.scale_kv_lora, **common)
            self._moe = SparseExpertsLayer(
                n_routed_experts=self.n_routed_experts,
                n_zero_experts=self.n_zero_experts, held=self.held,
                top_k=self.top_k, expert_width=self.expert_width,
                n_shared_experts=0,
                routed_scaling_factor=self.routed_scaling_factor,
                norm_topk_prob=False, scoring_func="softmax",
                router_bias=True, **common)
        return self._attn, self._moe

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        attn, moe = self._ensure_parts()
        ks = jax.random.split(key, 9)
        d, ff = self.n_in, self.intermediate_size
        t = InputType.recurrent(d)
        ones = lambda: jnp.ones((d,), dtypes.policy().param_dtype)
        mlp = lambda k: {"Wg": self._sample_w(k[0], (d, ff), d, ff),
                         "Wu": self._sample_w(k[1], (d, ff), d, ff),
                         "Wd": self._sample_w(k[2], (ff, d), ff, d)}
        p = {"moe": moe.initialize(ks[8], t)[0]}
        for i in (0, 1):
            p.update({f"norm_a{i}_gain": ones(),
                      f"norm_f{i}_gain": ones(),
                      f"attn{i}": attn.initialize(ks[i], t)[0],
                      f"mlp{i}": mlp(ks[2 + 3 * i:5 + 3 * i])})
        return p, {}

    def _forward(self, params, x, attend, active=None, stream=False):
        """The layer's equations; ``attend(i, z)`` is sub-layer
        ``i``'s attention over the normed ``z``; ``stream``: a serving
        step's call."""
        _, moe = self._ensure_parts()
        norm = lambda h, name: rms_norm(h, params[name], self.eps)
        mlp = lambda i, z: swiglu(z, params[f"mlp{i}"]["Wg"],
                                  params[f"mlp{i}"]["Wu"],
                                  params[f"mlp{i}"]["Wd"])
        x = x.astype(params["norm_a0_gain"].dtype)
        with jax.named_scope("mla0"):
            h = x + attend(0, norm(x, "norm_a0_gain"))
        z = norm(h, "norm_f0_gain")
        m, tally = moe.apply_tallied(params["moe"], z, active, stream)
        with jax.named_scope("mlp0"):
            h = h + mlp(0, z)
        with jax.named_scope("mla1"):
            h = h + attend(1, norm(h, "norm_a1_gain"))
        with jax.named_scope("mlp1"):
            h = h + mlp(1, norm(h, "norm_f1_gain")) + m
        return h, tally

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        attn, _ = self._ensure_parts()
        attend = lambda i, z: attn.apply(
            params[f"attn{i}"], {}, z, training=training, rng=rng,
            mask=mask)[0]
        return self._forward(params, x, attend)[0], state

    # ---- paged decode ----
    def zero_page_pool(self, n_pages: int, page_size: int, dtype):
        attn, _ = self._ensure_parts()
        return {f"a{i}": attn.zero_page_pool(n_pages, page_size, dtype)
                for i in (0, 1)}

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        """Both attentions are one layer object over two pools of one
        shape: its answer is the answer of both."""
        return self._ensure_parts()[0].paged_reads_by_table(
            page_size, t, dtype)

    def apply_stream_paged_aux(self, params, pool, table, pos, x,
                               active=None, n_valid=None):
        """(out, pool, tally): one decode step through both
        sub-layers; ``tally`` is the expert layer's
        (``SparseExpertsLayer.apply_tallied``) over the ``active``
        slots, or rows where the chunk program gives a (slots, t)
        mask beside its ``n_valid``."""
        attn, _ = self._ensure_parts()
        new_pool = {}

        def attend(i, z):
            a, new_pool[f"a{i}"] = attn.apply_stream_paged(
                params[f"attn{i}"], pool[f"a{i}"], table, pos, z,
                n_valid)
            return a

        h, tally = self._forward(params, x, attend, active, stream=True)
        return h, new_pool, tally

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        h, pool, _ = self.apply_stream_paged_aux(
            params, pool, table, pos, x, n_valid=n_valid)
        return h, pool


@register_layer
@dataclasses.dataclass
class GroupedQueryDecoderBlock(_ExpertsPart, BaseLayer):
    """Pre-RMSNorm decoder block ``h = x + GQA(norm(x)); y = h +
    F(norm(h))``: grouped-query attention
    (``GroupedQueryAttentionLayer``: global, or with ``window`` a
    sliding window whose paged cache is a slot-owned ring), then a
    dense SiLU-gated MLP (``n_routed_experts == 0``) or the expert
    layer with a sigmoid router, its selection-only correction bias,
    the selected weights normalised and no shared expert. The fields
    are the two sub-layers' own, flat, as ``LatentDecoderBlock`` has
    them."""

    n_in: Optional[int] = None
    eps: float = 1e-5
    # grouped-query attention (GroupedQueryAttentionLayer)
    n_heads: int = 4
    n_kv_heads: int = 2
    qk_head_dim: int = 8
    v_head_dim: int = 8
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    window: Optional[int] = None
    sink: bool = False
    value_scale: float = 1.0
    # dense MLP width (used when n_routed_experts == 0)
    intermediate_size: int = 128
    # expert layer (SparseExpertsLayer)
    n_routed_experts: int = 0
    held: Optional[Tuple[int, int]] = None
    top_k: int = 4
    expert_width: int = 32
    routed_scaling_factor: float = 1.0
    # the attention's score scale (None: qk_head_dim ** -0.5) and what
    # multiplies both branches before they join the residual stream
    softmax_scale: Optional[float] = None
    residual_multiplier: float = 1.0
    # an RMS norm over each query and key head, at the block's ``eps``
    qk_norm: bool = False

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    @property
    def stream_aux(self) -> bool:
        return self.n_routed_experts > 0

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = dict(n_in=self.n_in, weight_init=self.weight_init,
                          weight_distribution=self.weight_distribution)
            self._attn = GroupedQueryAttentionLayer(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                qk_head_dim=self.qk_head_dim,
                v_head_dim=self.v_head_dim, rotary_dim=self.rotary_dim,
                rope_theta=self.rope_theta, window=self.window,
                sink=self.sink, value_scale=self.value_scale,
                softmax_scale=self.softmax_scale,
                qk_norm=self.qk_norm, qk_norm_eps=self.eps, **common)
            self._moe = _biased_sigmoid_experts(self, self.held, common)
        return self._attn, self._moe

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        return _init_decoder_block(self, key, *self._ensure_parts())

    def _block(self, params, x, attend, active=None, stream=False):
        """The block's equations; ``attend(z)`` is the attention over
        the normed ``z``; ``stream``: a serving step's call."""
        x = x.astype(params["norm1_gain"].dtype)
        with jax.named_scope("attn/global" if self.window is None
                             else "attn/window"):
            a = attend(rms_norm(x, params["norm1_gain"], self.eps))
        return _ffn_half(
            params, _residual(x, a, self.residual_multiplier),
            self._ensure_parts()[1], self.eps, active,
            self.residual_multiplier, stream)

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        attn, _ = self._ensure_parts()
        attend = lambda z: attn.apply(params["attn"], {}, z,
                                      training=training, rng=rng,
                                      mask=mask)[0]
        return self._block(params, x, attend)[0], state

    # ---- paged decode ----
    def ring_pages(self, page_size: int) -> int:
        return self._ensure_parts()[0].ring_pages(page_size)

    def zero_page_pool(self, n_pages: int, page_size: int, dtype):
        return self._ensure_parts()[0].zero_page_pool(
            n_pages, page_size, dtype)

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        return self._ensure_parts()[0].paged_reads_by_table(
            page_size, t, dtype)

    def apply_stream_paged_aux(self, params, pool, table, pos, x,
                               active=None, n_valid=None):
        """(out, pool, counts), as
        ``LatentDecoderBlock.apply_stream_paged_aux``."""
        attn, _ = self._ensure_parts()
        new_pool = []

        def attend(z):
            a, p = attn.apply_stream_paged(params["attn"], pool, table,
                                           pos, z, n_valid)
            new_pool.append(p)
            return a

        h, counts = self._block(params, x, attend, active, stream=True)
        return h, new_pool[0], counts

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        h, pool, _ = self.apply_stream_paged_aux(
            params, pool, table, pos, x, n_valid=n_valid)
        return h, pool


class _SlotStateBlock(_ExpertsPart, BaseLayer):
    """Pre-RMSNorm decoder block ``h = x + m Mixer(norm(x)); y = h + m
    F(norm(h))`` over a sequence mixer whose paged cache is a row a
    SLOT (``zero_state_pool``), not pages: what it carries from token
    to token has a fixed size. ``F`` is the dense SiLU-gated MLP or,
    where the subclass has routed experts, their layer, whose counts
    then come out of the paged step (``stream_aux``). A subclass
    gives the fields, ``mixer`` (the mixer's scope, and its key in
    the parameters) and ``_ensure_parts() -> (mixer, experts or
    None)``."""

    # fields where a subclass has them
    residual_multiplier = 1.0
    n_routed_experts = 0

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    @property
    def stream_aux(self) -> bool:
        return self.n_routed_experts > 0

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        return _init_decoder_block(self, key, *self._ensure_parts(),
                                   mixer=self.mixer)

    def _block(self, params, x, mix, active=None, stream=False):
        """The block's equations; ``mix(z)`` is the mixer over the
        normed ``z``; ``stream``: a serving step's call."""
        x = x.astype(params["norm1_gain"].dtype)
        with jax.named_scope(self.mixer):
            a = mix(rms_norm(x, params["norm1_gain"], self.eps))
        m = self.residual_multiplier
        return _ffn_half(params, _residual(x, a, m),
                         self._ensure_parts()[1], self.eps, active, m,
                         stream)

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        mix = lambda z: self._ensure_parts()[0].apply(
            params[self.mixer], {}, z, training=training, rng=rng,
            mask=mask)[0]
        return self._block(params, x, mix)[0], state

    # ---- paged decode ----
    def zero_state_pool(self, slots: int, dtype):
        return self._ensure_parts()[0].zero_state_pool(slots, dtype)

    @property
    def chunk_rows_unrolled(self) -> bool:
        return getattr(self._ensure_parts()[0], "chunk_rows_unrolled",
                       False)

    def apply_stream_paged_aux(self, params, pool, table, pos, x,
                               active=None, n_valid=None):
        """(out, pool, counts), as
        ``LatentDecoderBlock.apply_stream_paged_aux``."""
        new_pool = []

        def mix(z):
            a, p = self._ensure_parts()[0].apply_stream_paged(
                params[self.mixer], pool, table, pos, z, n_valid)
            new_pool.append(p)
            return a

        h, counts = self._block(params, x, mix, active, stream=True)
        return h, new_pool[0], counts

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        h, pool, _ = self.apply_stream_paged_aux(
            params, pool, table, pos, x, n_valid=n_valid)
        return h, pool


@register_layer
@dataclasses.dataclass
class StateSpaceDecoderBlock(_SlotStateBlock):
    """``_SlotStateBlock`` over a Mamba-2 mixer (``Mamba2MixerLayer``,
    whose fields these are, flat) and the dense SiLU-gated MLP, both
    branches times ``residual_multiplier``."""

    n_in: Optional[int] = None
    eps: float = 1e-5
    # state-space mixer (Mamba2MixerLayer)
    n_heads: int = 4
    head_dim: int = 8
    state_size: int = 16
    n_groups: int = 1
    conv_width: int = 4
    # dense MLP width
    intermediate_size: int = 128
    residual_multiplier: float = 1.0

    mixer = "ssm"

    def _ensure_parts(self):
        if not hasattr(self, "_ssm"):
            self._ssm = Mamba2MixerLayer(
                n_in=self.n_in, n_heads=self.n_heads,
                head_dim=self.head_dim, state_size=self.state_size,
                n_groups=self.n_groups, conv_width=self.conv_width,
                eps=self.eps, weight_init=self.weight_init,
                weight_distribution=self.weight_distribution)
        return self._ssm, None


@register_layer
@dataclasses.dataclass
class ShortConvDecoderBlock(_SlotStateBlock):
    """``_SlotStateBlock`` over a gated short convolution
    (``ShortConvMixerLayer``), then the dense SiLU-gated MLP
    (``n_routed_experts == 0``) or ``GroupedQueryDecoderBlock``'s
    expert layer: LFM2's ``conv`` layer."""

    n_in: Optional[int] = None
    eps: float = 1e-5
    # gated short convolution (ShortConvMixerLayer)
    conv_width: int = 3
    # dense MLP width (used when n_routed_experts == 0)
    intermediate_size: int = 128
    # expert layer (SparseExpertsLayer), every expert held
    n_routed_experts: int = 0
    top_k: int = 4
    expert_width: int = 32
    routed_scaling_factor: float = 1.0

    mixer = "conv"

    def _ensure_parts(self):
        if not hasattr(self, "_conv"):
            common = dict(n_in=self.n_in, weight_init=self.weight_init,
                          weight_distribution=self.weight_distribution)
            self._conv = ShortConvMixerLayer(
                conv_width=self.conv_width, **common)
            self._moe = _biased_sigmoid_experts(self, None, common)
        return self._conv, self._moe
