"""Decoder blocks: a sequence mixer, then a feed-forward, each with
an RMS norm and joined to the residual stream.

Five of the six are ONE block, ``_NormedBlock``: ``h = x + m
Mixer(norm(x)); y = h + m F(norm(h))`` with ``F`` the dense SiLU-gated
MLP or the expert layer (``moe.SparseExpertsLayer``), or, where the
block's ``norm_placement`` is ``"post"``, each branch's OUTPUT normed
(``h = x + m norm(Mixer(x)); y = h + m norm(F(h))``: the Olmo
family's), or with ``"both"`` a norm on either side of each branch
(four gains: Trinity's and the Gemma line's). A class gives what a configuration adds: the two
sub-layers' own fields, flat, so that the block round-trips through
JSON like every DSL layer; the mixer's key in the parameters and its
``named_scope``; and ``_ensure_parts() -> (mixer, experts or None)``.

- ``LatentDecoderBlock``: latent attention (DeepSeek-V2 / V3).
- ``GroupedQueryDecoderBlock``: grouped-query attention, global or a
  sliding window with a learned sink, and a sigmoid router with a
  correction bias and no shared expert: MiMo-V2's layer; with
  ``qk_norm``, LFM2's attention layer; with ``qk_norm``, ``out_gate``,
  ``n_shared_experts`` and ``norm_placement`` ``"both"``, Trinity's
  (``afmoe``).
- ``StateSpaceDecoderBlock``: a Mamba-2 mixer (``state_space.py``) and
  the dense MLP; with ``GroupedQueryDecoderBlock`` and a
  ``residual_multiplier`` on both branches, Granite-4.0-H's two kinds
  of layer.
- ``ShortConvDecoderBlock``: a gated short convolution
  (``short_conv.py``) and the dense MLP or the expert layer: LFM2's
  ``conv`` layer.
- ``DeltaRuleDecoderBlock``: a gated delta-rule mixer
  (``delta_rule.py``) and the dense MLP; with
  ``GroupedQueryDecoderBlock``, ``norm_placement`` ``"post"`` and
  ``qk_norm`` ``"width"``, Olmo-Hybrid's two kinds of layer.

``ShortcutExpertBlock`` is LongCat-Flash's shortcut-connected layer
(arXiv 2509.01322 §2.2): two latent attentions and two dense MLPs in
sequence, and one expert layer that reads the first sub-layer's normed
hidden state and joins the residual stream at the end of the second.
Its equations are its own; everything else is ``_DecoderBlock``'s.

What a block keeps between the tokens of a served stream is what its
mixer keeps (``paged.MixerCacheLayer``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    GroupedQueryAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.delta_rule import (
    GatedDeltaMixerLayer)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    LatentAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.moe import (SparseExpertsLayer,
                                                   swiglu)
from deeplearning4j_tpu.nn.conf.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.conf.layers.paged import MixerCacheLayer
from deeplearning4j_tpu.nn.conf.layers.short_conv import (
    ShortConvMixerLayer)
from deeplearning4j_tpu.nn.conf.layers.state_space import Mamba2MixerLayer

__all__ = ["LatentDecoderBlock", "ShortcutExpertBlock",
           "GroupedQueryDecoderBlock", "StateSpaceDecoderBlock",
           "ShortConvDecoderBlock", "DeltaRuleDecoderBlock"]

_F32 = jnp.float32


def _residual(h, f, multiplier=1.0):
    """``h + multiplier * f`` in ``h``'s dtype; a multiplier that is
    not 1 scales in float32, so the sum is rounded once."""
    if multiplier == 1.0:
        return h + f
    return (h.astype(_F32) + multiplier * f.astype(_F32)).astype(h.dtype)


NORM_PLACEMENTS = ("pre", "post", "both")


def _pre(params, i, placement, eps, z):
    """The input of branch ``i`` (1 the mixer's, 2 the
    feed-forward's): ``z`` normed by ``norm<i>_gain``, or ``z`` itself
    where the branch's only norm sits behind it (``"post"``)."""
    if placement == "post":
        return z
    return rms_norm(z, params[f"norm{i}_gain"], eps)


def _post(params, i, placement, eps, f):
    """Branch ``i``'s output ``f`` as it joins the residual stream:
    as it is (``"pre"``), normed by ``norm<i>_gain`` (``"post"``) or
    by ``norm<i>_post_gain`` (``"both"``)."""
    if placement == "pre":
        return f
    return rms_norm(f, params[f"norm{i}_gain" if placement == "post"
                              else f"norm{i}_post_gain"], eps)


def _ffn_half(params, h, moe, eps, active=None, multiplier=1.0,
              stream=False, placement="pre"):
    """The second half of an RMS-normed decoder block,
    ``(h + multiplier * F(norm(h)), counts or None)`` with the norms
    where ``placement`` puts them (:func:`_pre`, :func:`_post`): ``F``
    is the expert layer ``moe`` (parameters ``params["moe"]``) or,
    where that is None, the dense SiLU-gated MLP ``Wg, Wu, Wd``.
    ``stream``: a serving step's call
    (``SparseExpertsLayer.apply_tallied``)."""
    z = _pre(params, 2, placement, eps, h)
    post = lambda f: _post(params, 2, placement, eps, f)
    if moe is None:
        with jax.named_scope("mlp"):
            f = swiglu(z, params["Wg"], params["Wu"], params["Wd"])
            return _residual(h, post(f), multiplier), None
    f, counts = moe.apply_counted(params["moe"], z, active, stream)
    return _residual(h, post(f), multiplier), counts


def _biased_sigmoid_experts(block, held, common):
    """The expert layer of ``block``'s flat fields over the ``held``
    share, or None where it has no routed experts: a sigmoid router
    with its selection-only correction bias, the selected weights
    normalised, and a shared expert only where the block has the
    field and sets it (MiMo-V2's and LFM2's have none)."""
    if not block.n_routed_experts:
        return None
    return SparseExpertsLayer(
        n_routed_experts=block.n_routed_experts, held=held,
        top_k=block.top_k, expert_width=block.expert_width,
        n_shared_experts=getattr(block, "n_shared_experts", 0),
        routed_scaling_factor=block.routed_scaling_factor,
        norm_topk_prob=True, scoring_func="sigmoid", router_bias=True,
        **common)


class _DecoderBlock(MixerCacheLayer, BaseLayer):
    """What a decoder block over ``_ensure_parts() -> (mixer, experts
    or None)`` is beside its equations: its shapes, what it says of
    its expert layer, and the mixer's cache passed on to the paged
    session. A subclass gives ``apply`` and ``apply_stream_paged_aux``."""

    n_routed_experts = 0        # a field where a subclass has experts

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def _common(self):
        """The fields every part takes from its block."""
        return dict(n_in=self.n_in, weight_init=self.weight_init,
                    weight_distribution=self.weight_distribution)

    def _mixer(self):
        return self._ensure_parts()[0]

    @property
    def held_experts(self) -> int:
        """How many routed experts the block holds: the width of the
        counts ``apply_with_counts`` gives; 0 for a dense block."""
        if not self.n_routed_experts:
            return 0
        held = getattr(self, "held", None)
        return held[1] if held else self.n_routed_experts

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        return self.apply_with_counts(
            params, state, x, training=training, rng=rng, mask=mask)[:2]

    @property
    def stream_aux(self) -> bool:
        """Does a decode step of this block return counts beside its
        output (``apply_stream_paged_aux``)? The paged session asks."""
        return self.n_routed_experts > 0

    def experts_grouped(self, rows: int, dtype) -> bool:
        """Does a serving step of ``rows`` rows run this block's
        experts as the grouped pass? The paged session asks, for
        ``serving_moe_grouped_steps_total``."""
        moe = self._ensure_parts()[1]
        return moe is not None and moe.takes_grouped_pass(rows, dtype)

    def experts_carry_rows(self, rows: int, dtype) -> bool:
        """Does a serving step of ``rows`` rows carry them through
        this block's experts on weights the pass reads anyway
        (``SparseExpertsLayer.carries_rows``)? The paged session asks,
        for the batcher's wide chunk program."""
        moe = self._ensure_parts()[1]
        return moe is not None and moe.carries_rows(rows, dtype)

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        h, pool, _ = self.apply_stream_paged_aux(
            params, pool, table, pos, x, n_valid=n_valid)
        return h, pool


class _NormedBlock(_DecoderBlock):
    """``h = x + m Mixer(norm(x)); y = h + m F(norm(h))``, ``m`` the
    ``residual_multiplier``, or with ``norm_placement`` ``"post"``
    ``h = x + m norm(Mixer(x)); y = h + m norm(F(h))`` (the mixer and
    the feed-forward read the residual stream as it is, each branch's
    output is normed before it joins), or with ``"both"`` ``h = x +
    m norm(Mixer(norm(x)))`` and the like for ``F`` (gains
    ``norm1_gain``, ``norm1_post_gain``, ``norm2_gain``,
    ``norm2_post_gain``): the equations, the parameters
    and both forms (whole sequence, paged step), once. A subclass
    gives the fields, ``mixer`` (the mixer's key in the parameters),
    ``scope`` (its ``named_scope``; ``mixer`` unless it says
    otherwise) and ``_ensure_parts``."""

    residual_multiplier = 1.0   # fields where a subclass has them
    norm_placement = "pre"

    def __post_init__(self):
        if self.norm_placement not in NORM_PLACEMENTS:
            raise ValueError(
                f"norm_placement {self.norm_placement!r}: one of "
                f"{NORM_PLACEMENTS}")

    @property
    def scope(self) -> str:
        return self.mixer

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        mixer, moe = self._ensure_parts()
        ka, km, k1, k2, k3 = jax.random.split(key, 5)
        d, ff = self.n_in, self.intermediate_size
        pd = dtypes.policy().param_dtype
        t = InputType.recurrent(d)
        p = {"norm1_gain": jnp.ones((d,), pd),
             "norm2_gain": jnp.ones((d,), pd),
             self.mixer: mixer.initialize(ka, t)[0]}
        if self.norm_placement == "both":
            p.update(norm1_post_gain=jnp.ones((d,), pd),
                     norm2_post_gain=jnp.ones((d,), pd))
        if moe is not None:
            p["moe"] = moe.initialize(km, t)[0]
        else:
            p.update(Wg=self._sample_w(k1, (d, ff), d, ff),
                     Wu=self._sample_w(k2, (d, ff), d, ff),
                     Wd=self._sample_w(k3, (ff, d), ff, d))
        return p, {}

    def _block(self, params, x, mix, active=None, stream=False):
        """The block's equations; ``mix(z)`` is the mixer over ``z``
        (the normed input, or the input itself where the only norm
        sits behind the mixer); ``stream``: a serving step's call."""
        x = x.astype(params["norm1_gain"].dtype)
        with jax.named_scope(self.scope):
            a = _post(params, 1, self.norm_placement, self.eps, mix(
                _pre(params, 1, self.norm_placement, self.eps, x)))
        m = self.residual_multiplier
        return _ffn_half(params, _residual(x, a, m),
                         self._ensure_parts()[1], self.eps, active, m,
                         stream, self.norm_placement)

    def apply_with_counts(self, params, state, x, *, training=False,
                          rng=None, mask=None):
        """``apply`` with a third value, the (held,) tokens each held
        expert took (None for a dense block): what a train step asks
        for (``MultiLayerNetwork._apply_in_train_step``)."""
        mix = lambda z: self._mixer().apply(
            params[self.mixer], {}, z, training=training, rng=rng,
            mask=mask)[0]
        h, counts = self._block(params, x, mix)
        return h, state, counts

    def apply_stream_paged_aux(self, params, pool, table, pos, x,
                               active=None, n_valid=None):
        """(out, pool, counts): one decode step through the block;
        ``counts`` is None for a dense block, else the (held,) tokens
        each held expert served among the ``active`` slots, or rows
        where the chunk program gives a (slots, t) mask beside its
        ``n_valid``."""
        new_pool = []

        def mix(z):
            a, p = self._mixer().apply_stream_paged(
                params[self.mixer], pool, table, pos, z, n_valid)
            new_pool.append(p)
            return a

        h, counts = self._block(params, x, mix, active, stream=True)
        return h, new_pool[0], counts


@register_layer
@dataclasses.dataclass
class LatentDecoderBlock(_NormedBlock):
    """``_NormedBlock`` over latent attention
    (``LatentAttentionLayer``), then a dense SiLU-gated MLP
    (``n_routed_experts == 0``) or the expert layer."""

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

    mixer = "attn"
    scope = "mla"

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = self._common()
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


@register_layer
@dataclasses.dataclass
class ShortcutExpertBlock(_DecoderBlock):
    """LongCat-Flash's shortcut-connected expert layer::

        h0 = x  + MLA_0(norm(x));   z0 = norm(h0)
        m  = MoE(z0)                    # the shortcut: read here ...
        h1 = h0 + MLP_0(z0)
        h2 = h1 + MLA_1(norm(h1))
        y  = h2 + MLP_1(norm(h2)) + m   # ... joined here

    Every norm has its own gain; the two attentions have their own
    weights and their own caches (``zero_pool`` gives ``{"a0", "a1"}``
    over one page table). The fields are the parts' own, flat."""

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

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = self._common()
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

    def apply_with_counts(self, params, state, x, *, training=False,
                          rng=None, mask=None):
        attn, _ = self._ensure_parts()
        attend = lambda i, z: attn.apply(
            params[f"attn{i}"], {}, z, training=training, rng=rng,
            mask=mask)[0]
        h, tally = self._forward(params, x, attend)
        return h, state, tally["held"]

    # ---- paged decode: both attentions are one layer object over
    #      two pools of one shape, so its declaration and its
    #      ``paged_reads_by_table`` are the answers of both ----
    def zero_pool(self, n_pages: int, page_size: int, dtype):
        attn, _ = self._ensure_parts()
        return {f"a{i}": attn.zero_pool(n_pages, page_size, dtype)
                for i in (0, 1)}

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


@register_layer
@dataclasses.dataclass
class GroupedQueryDecoderBlock(_NormedBlock):
    """``_NormedBlock`` over grouped-query attention
    (``GroupedQueryAttentionLayer``: global, or with ``window`` a
    sliding window whose paged cache is a slot-owned ring; with
    ``out_gate`` its output gate), then a dense SiLU-gated MLP
    (``n_routed_experts == 0``) or the expert layer with a sigmoid
    router, its selection-only correction bias, the selected weights
    normalised and ``n_shared_experts`` shared experts (none unless
    set)."""

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
    # an RMS norm at the block's ``eps`` over each query and key head
    # (True) or over the whole projected width ("width")
    qk_norm: Union[bool, str] = False
    norm_placement: str = "pre"
    out_gate: bool = False
    n_shared_experts: int = 0

    mixer = "attn"

    @property
    def scope(self) -> str:
        return "attn/global" if self.window is None else "attn/window"

    def _ensure_parts(self):
        if not hasattr(self, "_attn"):
            common = self._common()
            self._attn = GroupedQueryAttentionLayer(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                qk_head_dim=self.qk_head_dim,
                v_head_dim=self.v_head_dim, rotary_dim=self.rotary_dim,
                rope_theta=self.rope_theta, window=self.window,
                sink=self.sink, value_scale=self.value_scale,
                softmax_scale=self.softmax_scale,
                qk_norm=self.qk_norm, qk_norm_eps=self.eps,
                out_gate=self.out_gate, **common)
            self._moe = _biased_sigmoid_experts(self, self.held, common)
        return self._attn, self._moe


@register_layer
@dataclasses.dataclass
class StateSpaceDecoderBlock(_NormedBlock):
    """``_NormedBlock`` over a Mamba-2 mixer (``Mamba2MixerLayer``,
    whose fields these are, flat) and the dense SiLU-gated MLP."""

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
                n_heads=self.n_heads, head_dim=self.head_dim,
                state_size=self.state_size, n_groups=self.n_groups,
                conv_width=self.conv_width, eps=self.eps,
                **self._common())
        return self._ssm, None


@register_layer
@dataclasses.dataclass
class ShortConvDecoderBlock(_NormedBlock):
    """``_NormedBlock`` over a gated short convolution
    (``ShortConvMixerLayer``), then the dense SiLU-gated MLP
    (``n_routed_experts == 0``) or ``GroupedQueryDecoderBlock``'s
    expert layer, every expert held: LFM2's ``conv`` layer."""

    n_in: Optional[int] = None
    eps: float = 1e-5
    # gated short convolution (ShortConvMixerLayer)
    conv_width: int = 3
    # dense MLP width (used when n_routed_experts == 0)
    intermediate_size: int = 128
    # expert layer (SparseExpertsLayer)
    n_routed_experts: int = 0
    top_k: int = 4
    expert_width: int = 32
    routed_scaling_factor: float = 1.0

    mixer = "conv"

    def _ensure_parts(self):
        if not hasattr(self, "_conv"):
            common = self._common()
            self._conv = ShortConvMixerLayer(
                conv_width=self.conv_width, **common)
            self._moe = _biased_sigmoid_experts(self, None, common)
        return self._conv, self._moe


@register_layer
@dataclasses.dataclass
class DeltaRuleDecoderBlock(_NormedBlock):
    """``_NormedBlock`` over a gated delta-rule mixer
    (``GatedDeltaMixerLayer``, whose fields these are, flat) and the
    dense SiLU-gated MLP: Olmo-Hybrid's ``linear_attention`` layer
    with ``norm_placement`` ``"post"``."""

    n_in: Optional[int] = None
    eps: float = 1e-6
    # gated delta-rule mixer (GatedDeltaMixerLayer)
    n_heads: int = 4
    key_head_dim: int = 8
    value_head_dim: int = 16
    conv_width: int = 4
    allow_neg_eigval: bool = False
    # dense MLP width
    intermediate_size: int = 128
    norm_placement: str = "pre"

    mixer = "delta"

    def _ensure_parts(self):
        if not hasattr(self, "_delta"):
            self._delta = GatedDeltaMixerLayer(
                n_heads=self.n_heads, key_head_dim=self.key_head_dim,
                value_head_dim=self.value_head_dim,
                conv_width=self.conv_width,
                allow_neg_eigval=self.allow_neg_eigval, eps=self.eps,
                **self._common())
        return self._delta, None
