"""Attention layers — transformer building blocks.

The 2017 reference predates transformers (its long-context story is
tBPTT + masking, SURVEY §5); attention layers are a required capability
extension of the TPU rebuild. ``SelfAttentionLayer`` is multi-head
self-attention over (B,T,C) inputs backed by the Pallas flash kernel on
TPU (ops/attention.py — the framework's hand-written-kernel seam);
``TransformerEncoderLayer`` is the full pre-LN block (MHA + MLP with
residuals) so the config DSL can express transformer stacks.
``GroupedQueryAttentionLayer`` is causal attention whose query heads
share fewer key/value heads, of a key width and a value width of their
own, with rotary positions, an optional sliding window and an optional
learned sink; a window layer's paged cache is a ring of pages a slot
owns, not pages of the allocator.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.paged import (PAGES, RING,
                                                     MixerCacheLayer,
                                                     PagedCache, PagedLayer)

__all__ = ["SelfAttentionLayer", "TransformerEncoderLayer",
           "GroupedQueryAttentionLayer"]


from deeplearning4j_tpu.nn.conf.layers.normalization import (
    layer_norm as _layer_norm, rms_norm)
from deeplearning4j_tpu.nn.conf.layers.rotary import rope, yarn_inv_freq


def paged_write_targets(table, pos, t, page_size, n_valid=None):
    """Where a paged step writes the ``t`` rows of every slot:
    ``(wpos, page_ids, offs)``, each (S, t). Row ``j`` of slot ``s``
    is position ``pos[s] + j``, in page ``table[s, wpos // page_size]``
    at offset ``wpos % page_size``.

    ``n_valid`` (S,) is given by the chunk program, whose rows at or
    past a slot's ``n_valid`` carry no token (a prompt's ragged tail,
    the t - 1 spare rows of a slot in decode, every row of a free
    slot): they go to the scratch page 0, which no slot reads unmasked.
    Their own table lookup is not trusted: near capacity
    ``wpos // page_size`` runs past the table's width, where a clamped
    lookup would land in the slot's last live page."""
    wpos = pos[:, None] + jnp.arange(t)[None, :]
    if n_valid is None:
        page_ids = jnp.take_along_axis(table, wpos // page_size, axis=1)
    else:
        page_ids = jnp.where(
            jnp.arange(t)[None, :] < n_valid[:, None],
            jnp.take_along_axis(
                table, jnp.minimum(wpos // page_size,
                                   table.shape[1] - 1), axis=1),
            0)
    return wpos, page_ids, wpos % page_size


@register_layer
@dataclasses.dataclass
class SelfAttentionLayer(PagedLayer, BaseLayer):
    """Multi-head self-attention, (B,T,C) → (B,T,n_out)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None        # model dim (defaults to n_in)
    n_heads: int = 4
    causal: bool = False
    # biases on the q/k/v projections (Keras MultiHeadAttention
    # default; our native transformer blocks keep them off)
    qkv_bias: bool = False
    # bias on the output projection. Kept separate from qkv_bias so a
    # Keras MultiHeadAttention(use_bias=False) import has the SAME
    # trainable surface as the source model — a zero-initialized bo
    # matches at inference but would train a parameter Keras doesn't
    # have (ADVICE r4)
    out_bias: bool = True

    seq_parallelizable = True          # attention rides the ring

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size
        if self.n_out is None:
            self.n_out = self.n_in

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by "
                             f"n_heads {self.n_heads}")
        kq, kk, kv, ko = jax.random.split(key, 4)
        pd = dtypes.policy().param_dtype
        d = self.n_out
        p = {
            "Wq": self._sample_w(kq, (self.n_in, d), self.n_in, d),
            "Wk": self._sample_w(kk, (self.n_in, d), self.n_in, d),
            "Wv": self._sample_w(kv, (self.n_in, d), self.n_in, d),
            "Wo": self._sample_w(ko, (d, d), d, d),
        }
        if self.out_bias:
            p["bo"] = jnp.zeros((d,), pd)
        if self.qkv_bias:
            p["bq"] = jnp.zeros((d,), pd)
            p["bk"] = jnp.zeros((d,), pd)
            p["bv"] = jnp.zeros((d,), pd)
        return p, {}

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.attention import (flash_attention,
                                                      mesh_island)
        x = self.apply_input_dropout(x, training=training, rng=rng)
        B, T, _ = x.shape
        q, k, v = self._project_qkv(params, x)
        from deeplearning4j_tpu.parallel.seq_context import (
            current_mesh, current_seq_axis)
        seq_axis = current_seq_axis()
        mesh = current_mesh()
        if seq_axis is not None and mesh is not None:
            # GSPMD-mode sequence parallelism (seq composed with
            # dp/tp): the step is a plain jit, so the ring gets its
            # own shard_map ISLAND — time over the seq axis, batch
            # and heads over data/model — while everything outside
            # it stays automatic, which is what lets Megatron
            # head-sharded projections compose with the ring
            # (seq_context.current_mesh docstring).
            from deeplearning4j_tpu.parallel.ring_attention import (
                ring_self_attention)
            causal = self.causal

            def island(qc, kc, vc, mc=None):
                o = ring_self_attention(qc, kc, vc, axis_name=seq_axis,
                                        causal=causal, kv_mask=mc)
                return o if mc is None else o * mc[:, :, None, None]

            out = mesh_island(island, mesh, q, k, v, mask,
                              seq_axis=seq_axis)
        elif seq_axis is not None:
            # manual sequence-parallel step: x is the LOCAL (B, T/n, C)
            # chunk of a sequence sharded over `seq_axis`; attention
            # must span the whole distributed sequence, so ride the
            # ring (exact, differentiable, kernels on TPU). A
            # key-padding mask chunk rotates with its K/V block; padded
            # query rows are zeroed here (Layer.java:317 contract).
            from deeplearning4j_tpu.parallel.ring_attention import (
                ring_self_attention)
            out = ring_self_attention(q, k, v, axis_name=seq_axis,
                                      causal=self.causal,
                                      kv_mask=mask)
            if mask is not None:
                out = out * mask[:, :, None, None]
        elif mask is not None:
            # padded keys must leave the softmax DENOMINATOR, not just
            # contribute zero values — zeroing k/v would still give each
            # masked position weight exp(0) and dilute every real token.
            # The kv_mask-aware kernels handle this exactly, so
            # variable-length batches KEEP the flash kernel; padded
            # query rows are zeroed here (Layer.java:317 contract).
            out = flash_attention(q, k, v, causal=self.causal,
                                  kv_mask=mask)
            out = out * mask[:, :, None, None]
        else:
            out = flash_attention(q, k, v, causal=self.causal)
        out = out.reshape(B, T, self.n_out)
        proj = out @ params["Wo"]
        if self.out_bias:
            proj = proj + params["bo"]
        return proj, state

    def _project_flat(self, params, x):
        """The shared q/k/v projection (+optional biases), heads side
        by side in the last axis — ONE implementation for apply and
        the streaming variants, so full-sequence and streaming
        outputs cannot drift."""
        q = x @ params["Wq"]
        k = x @ params["Wk"]
        v = x @ params["Wv"]
        if self.qkv_bias:
            q = q + params["bq"]
            k = k + params["bk"]
            v = v + params["bv"]
        return q, k, v

    def _project_qkv(self, params, x):
        """:meth:`_project_flat` with the head split: three
        (B, T, H, Dh)."""
        B, T, _ = x.shape
        H = self.n_heads
        Dh = self.n_out // H
        return tuple(y.reshape(B, T, H, Dh)
                     for y in self._project_flat(params, x))

    # ---- stateful streaming inference (rnnTimeStep contract,
    #      MultiLayerNetwork.java:2656): the attention analog of a
    #      recurrent carry is the KV CACHE ----
    def apply_stream(self, params, cache, x):
        """Incremental decode: ``x`` is the NEW (B, t, C) chunk;
        ``cache`` holds the k/v history (None at sequence start).
        Returns (out, new_cache); feeding chunks sequentially equals
        one full-sequence causal forward (tested). Eager-mode path
        (rnn_time_step is not jitted), so the cache grows by concat —
        no static max length needed. Requires causal=True: streaming
        non-causal attention would need future tokens."""
        if not self.causal:
            raise ValueError(
                "apply_stream requires causal=True: non-causal "
                "attention needs future timesteps — use output() on "
                "the full sequence instead")
        B, t, _ = x.shape
        q, k, v = self._project_qkv(params, x)
        if cache is None:
            n_cached = 0
            k_full, v_full = k, v
        else:
            n_cached = cache["k"].shape[1]
            k_full = jnp.concatenate([cache["k"], k], axis=1)
            v_full = jnp.concatenate([cache["v"], v], axis=1)
        out = _stream_attention(q, k_full, v_full, n_cached)
        out = out.reshape(B, t, self.n_out)
        proj = out @ params["Wo"]
        if self.out_bias:
            proj = proj + params["bo"]
        return proj, {"k": k_full, "v": v_full}

    # ---- jitted bounded-cache streaming (round-4 verdict weak #7:
    #      the eager concat cache is O(T^2) copy traffic with a
    #      dispatch per token; this variant carries a FIXED-capacity
    #      cache with static shapes so the whole token step jits) ----
    def zero_stream_cache(self, batch: int, capacity: int, dtype):
        H = self.n_heads
        Dh = self.n_out // H
        # two DISTINCT buffers: the session donates the cache to the
        # jitted step, and donating one aliased array twice is a
        # runtime error
        return {"k": jnp.zeros((batch, capacity, H, Dh), dtype),
                "v": jnp.zeros((batch, capacity, H, Dh), dtype)}

    def apply_stream_bounded(self, params, cache, x, pos):
        """One jittable decode step: ``x`` is the new (B, t, C) chunk,
        ``cache`` a fixed-capacity {'k','v'} of shape (B, CAP, H, Dh),
        ``pos`` the traced count of valid cached tokens. Writes the
        chunk at [pos, pos+t) in place (dynamic_update_slice — O(t)
        traffic, vs the eager path's O(pos) concat) and attends the
        new queries over the full capacity with a single positional
        mask: query i (global pos+i) sees keys k_pos <= pos+i, which
        simultaneously hides unwritten tail slots, stale slots past
        pos+t, and in-chunk future tokens. Returns (out, cache) —
        the caller advances pos. Capacity bounds are the CALLER's to
        enforce (they are static host decisions; see
        models/streaming.py)."""
        if not self.causal:
            raise ValueError(
                "apply_stream_bounded requires causal=True: streaming "
                "non-causal attention would need future timesteps")
        B, t, _ = x.shape
        q, k, v = self._project_qkv(params, x)
        zero = jnp.zeros((), jnp.int32)
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype),
            (zero, pos, zero, zero))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype),
            (zero, pos, zero, zero))
        cap = k_cache.shape[1]
        scale = q.shape[-1] ** -0.5
        from deeplearning4j_tpu.ops.attention import _NEG_INF
        logits = jnp.einsum("bqhd,bkhd->bhqk", q,
                            k_cache.astype(q.dtype)) * scale
        k_pos = jnp.arange(cap)[None, :]
        q_pos = pos + jnp.arange(t)[:, None]
        logits = jnp.where((k_pos <= q_pos)[None, None], logits,
                           _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         v_cache.astype(q.dtype))
        out = out.reshape(B, t, self.n_out)
        proj = out @ params["Wo"]
        if self.out_bias:
            proj = proj + params["bo"]
        return proj, {"k": k_cache, "v": v_cache}

    # ---- paged (block) KV cache: the vLLM memory model over the
    #      same math as apply_stream_bounded. The session owns ONE
    #      physical pool of fixed-size pages per layer; each slot's
    #      cache is the pages its table names, read in place — so KV
    #      memory is bounded by the pool, not by slots x max-capacity
    #      (models/paged_kv.py), and a step's KV traffic by the
    #      tokens the slots hold (ops/paged_attention.py) ----
    def paged_cache(self, page_size: int) -> PagedCache:
        return PagedCache(PAGES)

    def zero_pool(self, n_pages: int, page_size: int, dtype):
        """Physical page pool for this layer: {'k','v'} of
        (n_pages, page_size, H * Dh). A page is ``page_size`` rows of
        all heads side by side, as the projections leave them: one
        contiguous, lane-dense block for the by-table kernel to
        fetch. Two DISTINCT buffers (see ``zero_stream_cache``)."""
        shape = (n_pages, page_size, self.n_out)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        """Will ``apply_stream_paged`` at ``t`` rows a slot read each
        slot's live pages by table (True) or gather every slot's whole
        capacity (False)? The session's accounting asks."""
        from deeplearning4j_tpu.ops.paged_attention import reads_by_table
        return reads_by_table(self.n_heads, self.n_out // self.n_heads,
                              page_size, t, dtype)

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One jittable decode step over paged caches for ALL slots at
        once. ``x`` is the new (S, t, C) chunk (one row per slot),
        ``pool`` the physical {'k','v'} pages of shape
        (n_pages, page_size, H * Dh), ``table`` the (S, P) per-slot
        page table, ``pos`` the (S,) per-slot token positions,
        ``n_valid`` (S,) how many of a slot's t rows carry a token
        (None: all of them; see :func:`paged_write_targets`). Writes
        each slot's new k/v at its (page, offset) — scatter indices
        are unique because written pages are slot-exclusive (shared
        prefix pages are read-only; divergence is copy-on-write at
        admission, host-side) — then attends each slot's queries over
        the positions the slot holds, with the same k_pos <= q_pos
        mask as the dense step: on a TPU page by page through the
        slot's table and no further than its length, elsewhere over
        the slot's GATHERED virtual cache of P*page_size positions
        (``ops/paged_attention.py``; the gather is the tests' oracle
        for the kernel). With P*page_size == dense capacity the math
        is position-for-position identical to apply_stream_bounded
        (greedy-token parity is tested). Returns (out, pool)."""
        if not self.causal:
            raise ValueError(
                "apply_stream_paged requires causal=True: streaming "
                "non-causal attention would need future timesteps")
        from deeplearning4j_tpu.ops.paged_attention import paged_attention
        t = x.shape[1]
        ps = pool["k"].shape[1]
        q, k, v = self._project_flat(params, x)
        # write positions for the t new tokens of every slot
        _, page_ids, offs = paged_write_targets(table, pos, t, ps,
                                                n_valid)
        k_pool = pool["k"].at[page_ids, offs].set(
            k.astype(pool["k"].dtype))
        v_pool = pool["v"].at[page_ids, offs].set(
            v.astype(pool["v"].dtype))
        out = paged_attention(q, k_pool, v_pool, table, pos, n_valid,
                              n_heads=self.n_heads)
        proj = out @ params["Wo"]
        if self.out_bias:
            proj = proj + params["bo"]
        return proj, {"k": k_pool, "v": v_pool}


def ring_write_targets(table, pos, t, page_size, ring_pages,
                       n_valid=None):
    """:func:`paged_write_targets` for a layer whose cache is a RING
    of ``ring_pages`` pages a slot: ``(wpos, page_ids, offs, last)``.
    Slot ``s`` owns the physical pages ``1 + s * ring_pages ..
    (s + 1) * ring_pages`` whatever its table says, and position ``p``
    lives at ring row ``p mod (ring_pages * page_size)`` of them.
    ``last`` (S,) is the last position the step writes for a slot
    (``pos - 1`` where it writes none).

    Rows that carry no token go to the scratch page 0: those at or
    past ``n_valid`` in the chunk program; in the single-row program,
    which has no ``n_valid``, the row of a slot that sits the step
    out, which the session marks by an all-zero table row (the
    allocator never hands out page 0)."""
    n_slots = table.shape[0]
    span = ring_pages * page_size
    wpos = pos[:, None] + jnp.arange(t)[None, :]
    if n_valid is None:
        live = jnp.broadcast_to(table[:, :1] > 0, wpos.shape)
        last = pos + (t - 1)
    else:
        live = jnp.arange(t)[None, :] < n_valid[:, None]
        last = pos + n_valid - 1
    first = 1 + jnp.arange(n_slots)[:, None] * ring_pages
    row = wpos % span
    page_ids = jnp.where(live, first + row // page_size, 0)
    return wpos, page_ids, row % page_size, last


@register_layer
@dataclasses.dataclass
class GroupedQueryAttentionLayer(PagedLayer, BaseLayer):
    """Causal grouped-query attention, (B,T,C) -> (B,T,C): ``n_heads``
    query heads of ``qk_head_dim`` over ``n_kv_heads`` key heads of
    ``qk_head_dim`` and value heads of ``v_head_dim``; query head
    ``i`` reads key/value head ``i // (n_heads / n_kv_heads)``. No
    bias anywhere.

    ``rotary_dim``: the first so many values of every query and key
    head are rotated by position (half-split pairs, ``rope_theta``);
    0 gives no positions. ``value_scale`` multiplies the values
    before they are cached. ``window``: query ``i`` sees keys ``j``
    with ``i - window < j <= i``; None sees every ``j <= i``.
    ``sink``: a learned logit a query head (parameter ``sink``) that
    joins the softmax's denominator and adds no value.
    ``softmax_scale`` multiplies the scores; None is
    ``qk_head_dim ** -0.5``. ``qk_norm``: every query head and every
    key head is RMS-normed over its ``qk_head_dim`` values before the
    rotation (one gain for all query heads, ``q_norm_gain``, and one
    for all key heads, ``k_norm_gain``; ``qk_norm_eps``), so the cache
    holds normed keys; ``"width"`` norms the whole projected width
    instead (all heads' values as one vector, gains of ``H dq`` and
    ``K dq``: the Olmo family's). ``out_gate``: the heads' output is
    multiplied by ``sigmoid(x Wgate)``, ``x`` the layer's input and
    ``Wgate`` (C, H dv), before the output projection (``_out``).

    Two forms of one mathematics: ``apply`` attends over the whole
    sequence, ``apply_stream_paged`` over a paged cache that holds
    rotated keys and scaled values. Both go through ``_project``,
    ``_attend`` (exact einsum, float32 scores and softmax) and
    ``_out``. On a TPU ``apply`` takes the flash kernels' band
    (``ops/attention.py``: the window's tiles alone, a key head read
    by its query heads without being repeated) where ``_takes_flash``
    admits the shapes, and a layer without ``window`` and ``sink``
    reads its paged cache through the grouped by-table kernel of
    ``ops/paged_attention.py``, the same mathematics page by page;
    ``_attend`` is the oracle of both.

    A layer with a ``window`` keeps its cache in a RING: the session
    gives it ``slots * ring_pages + 1`` pages and slot ``s`` owns
    pages ``1 + s * ring_pages ..``, position ``p`` at ring row
    ``p mod (ring_pages * page_size)``. Visibility is decided by the
    POSITION a ring row holds, worked out from ``pos``, so a row left
    by an earlier tenant or by a position since overwritten is never
    seen and nothing is ever zeroed."""

    n_in: Optional[int] = None
    n_heads: int = 4
    n_kv_heads: int = 2
    qk_head_dim: int = 8
    v_head_dim: int = 8
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    window: Optional[int] = None
    sink: bool = False
    value_scale: float = 1.0
    softmax_scale: Optional[float] = None
    qk_norm: Union[bool, str] = False
    qk_norm_eps: float = 1e-6
    out_gate: bool = False

    def __post_init__(self):
        if self.qk_norm not in (False, True, "width"):
            raise ValueError(f"qk_norm {self.qk_norm!r}: False, True "
                             "(a head) or 'width'")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_kv_heads {self.n_kv_heads}")
        if self.rotary_dim % 2 or self.rotary_dim > self.qk_head_dim:
            raise ValueError(
                f"rotary_dim {self.rotary_dim}: even and at most "
                f"qk_head_dim {self.qk_head_dim}")
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        d, H, K = self.n_in, self.n_heads, self.n_kv_heads
        dq, dv = self.qk_head_dim, self.v_head_dim
        ks = jax.random.split(key, 4)
        w = lambda k, a, b: self._sample_w(k, (a, b), a, b)
        p = {"Wq": w(ks[0], d, H * dq), "Wk": w(ks[1], d, K * dq),
             "Wv": w(ks[2], d, K * dv), "Wo": w(ks[3], H * dv, d)}
        if self.sink:
            p["sink"] = jnp.zeros((H,), dtypes.policy().param_dtype)
        if self.out_gate:
            p["Wgate"] = self._sample_w(jax.random.fold_in(key, 4),
                                        (d, H * dv), d, H * dv)
        if self.qk_norm:
            wide = self.qk_norm == "width"
            for name, n in (("q_norm_gain", H), ("k_norm_gain", K)):
                p[name] = jnp.ones((n * dq if wide else dq,),
                                   dtypes.policy().param_dtype)
        return p, {}

    # ---- pieces shared by both forms ----
    def _rotate(self, y, positions):
        """y (B,t,N,dq) at ``positions`` (B,t)."""
        r = self.rotary_dim
        if not r:
            return y
        inv = jnp.asarray(yarn_inv_freq(r, self.rope_theta, None))
        return jnp.concatenate(
            [rope(y[..., :r], positions[:, :, None], inv, halves=True),
             y[..., r:]], axis=-1)

    def _project(self, params, x, positions):
        """x (B,t,C) at ``positions`` (B,t) -> q (B,t,H,dq) and k
        (B,t,K,dq), normed (a head, or the whole width) where
        ``qk_norm`` and rotated, v (B,t,K,dv) scaled: k and v are what
        the cache holds."""
        B, t, _ = x.shape
        H, K = self.n_heads, self.n_kv_heads
        x = x.astype(params["Wq"].dtype)
        norm = lambda y, gain: rms_norm(y, params[gain], self.qk_norm_eps)

        def project(name, gain, n):
            y = x @ params[name]
            if self.qk_norm == "width":
                y = norm(y, gain)
            return y.reshape(B, t, n, self.qk_head_dim)

        q = project("Wq", "q_norm_gain", H)
        k = project("Wk", "k_norm_gain", K)
        if self.qk_norm is True:
            q, k = norm(q, "q_norm_gain"), norm(k, "k_norm_gain")
        if self.value_scale == 1.0:
            v = x @ params["Wv"]
        else:
            # scaled in float32, rounded once
            v = (einsum_f32("btc,cn->btn", x, params["Wv"])
                 * self.value_scale).astype(x.dtype)
        return (self._rotate(q, positions), self._rotate(k, positions),
                v.reshape(B, t, K, self.v_head_dim))

    def _attend(self, params, q, k, v, q_pos, k_pos):
        """q (B,t,H,dq) at ``q_pos`` (B,t) over keys k (B,N,K,dq) and
        values v (B,N,K,dv) that hold the positions ``k_pos`` (B,N):
        key ``j`` is visible to query ``i`` iff ``0 <= k_pos[j] <=
        q_pos[i]`` and, with a window, ``k_pos[j] > q_pos[i] -
        window``. Returns the heads' output side by side,
        (B,t,H dv)."""
        from deeplearning4j_tpu.ops.attention import _NEG_INF
        B, t, H, dq = q.shape
        K = self.n_kv_heads
        k, v = k.astype(q.dtype), v.astype(q.dtype)
        s = einsum_f32("btkgd,bnkd->bkgtn",
                       q.reshape(B, t, K, H // K, dq), k) * (
            dq ** -0.5 if self.softmax_scale is None
            else self.softmax_scale)
        qp, kp = q_pos[:, :, None], k_pos[:, None, :]
        seen = (kp >= 0) & (kp <= qp)
        if self.window is not None:
            seen = seen & (kp > qp - self.window)
        s = jnp.where(seen[:, None, None], s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        if self.sink:
            b = params["sink"].astype(jnp.float32).reshape(
                1, K, H // K, 1, 1)
            m = jnp.maximum(m, b)
        e = jnp.exp(s - m)
        z = jnp.sum(e, axis=-1, keepdims=True)
        if self.sink:
            z = z + jnp.exp(b - m)
        o = jnp.einsum("bkgtn,bnkd->btkgd", (e / z).astype(v.dtype), v)
        return o.reshape(B, t, -1)

    def _out(self, params, o, x):
        """The heads' output ``o`` (B,t,H dv) -> (B,t,C): gated by the
        layer's input ``x`` where ``out_gate`` (float32 gate, rounded
        once), then projected."""
        if self.out_gate:
            gate = jax.nn.sigmoid(einsum_f32(
                "btc,cn->btn", x.astype(params["Wgate"].dtype),
                params["Wgate"]))
            o = (o * gate).astype(o.dtype)
        return o @ params["Wo"]

    def _takes_flash(self, T: int) -> bool:
        """Does ``apply`` over ``T`` positions go through the flash
        kernels? On a TPU, for one head size, no sink, and a sequence
        of whole tiles of 128 or more (``ops.attention._auto_block``);
        the score scale, where the layer has its own, goes into q."""
        from deeplearning4j_tpu.ops.attention import (_auto_block,
                                                      _use_pallas)
        block = _auto_block(T, self.qk_head_dim)
        return (not self.sink and self.qk_head_dim == self.v_head_dim
                and block >= 128 and _use_pallas(T, block, block))

    # ---- full sequence ----
    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        if mask is not None:
            raise NotImplementedError(
                "GroupedQueryAttentionLayer has no key-padding mask: "
                "feed sequences of one length")
        x = self.apply_input_dropout(x, training=training, rng=rng)
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q, k, v = self._project(params, x, pos)
        if self._takes_flash(T):
            from deeplearning4j_tpu.ops.attention import flash_attention
            if self.softmax_scale is not None:
                q = q * (self.softmax_scale * self.qk_head_dim ** 0.5)
            o = flash_attention(q, k, v, causal=True,
                                window=self.window).reshape(B, T, -1)
        else:
            o = self._attend(params, q, k, v, pos, pos)
        return self._out(params, o, x), state

    # ---- paged cache ----
    def paged_cache(self, page_size: int) -> PagedCache:
        """Without a window the allocator's pages; with one a ring of
        the window's pages and one more, so that a step of up to
        ``ring_pages * page_size - window + 1`` rows a slot overwrites
        no position one of its own rows still reads."""
        if self.window is None:
            return PagedCache(PAGES)
        return PagedCache(RING, -(-self.window // page_size) + 1)

    def _value_lanes(self, page_size: int, dtype) -> int:
        """The width a value head takes in the paged pool:
        ``v_head_dim``, or that rounded up to whole lane tiles (zeros
        behind the values) where that alone lets the layer read its
        pages by table: a row of the kernel's output is one value
        head."""
        dv = self.v_head_dim
        tiled = -(-dv // 128) * 128
        if tiled != dv and self._by_table(tiled, page_size, 1, dtype):
            return tiled
        return dv

    def _by_table(self, lanes: int, page_size: int, t: int, dtype) -> bool:
        """``paged_reads_by_table`` over a pool whose value heads are
        ``lanes`` wide."""
        from deeplearning4j_tpu.ops.paged_attention import \
            grouped_reads_by_table
        return (self.window is None and not self.sink
                and grouped_reads_by_table(
                    self.n_heads, self.n_kv_heads, self.qk_head_dim,
                    lanes, page_size, t, dtype))

    def zero_pool(self, n_pages: int, page_size: int, dtype):
        """{'k': (n_pages, page_size, K * dq), 'v': (.., K * dv)}:
        rotated keys and scaled values, heads side by side (a value
        head as wide as ``_value_lanes`` says)."""
        K = self.n_kv_heads
        return {"k": jnp.zeros((n_pages, page_size,
                                K * self.qk_head_dim), dtype),
                "v": jnp.zeros((n_pages, page_size,
                                K * self._value_lanes(page_size, dtype)),
                               dtype)}

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        """Will ``apply_stream_paged`` at ``t`` rows a slot read each
        slot's live pages by table (True), or its whole table or ring
        (False)? A predicate of the shapes
        (``ops.paged_attention.grouped_reads_by_table``); a layer with
        a ``window`` or a ``sink`` keeps ``_attend``: the kernel has
        neither a first position nor a logit in its denominator."""
        return self._by_table(self._value_lanes(page_size, dtype),
                              page_size, t, dtype)

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One step for all slots over the paged cache (the
        ``SelfAttentionLayer.apply_stream_paged`` contract). Without a
        window the slot's keys are the pages its table names: read
        page by page and no further than its length where
        ``paged_reads_by_table`` holds (``ops/paged_attention.py``'s
        grouped kernel: the same mathematics as ``_attend``, which is
        its oracle), gathered whole elsewhere; with one they are the
        slot's own ring (:func:`ring_write_targets`), whose pool the
        session sized by ``paged_cache``: a chunk wider than the ring
        has room for raises here, at trace time. Returns (out, pool)."""
        S, t, _ = x.shape
        ps = pool["k"].shape[1]
        K = self.n_kv_heads
        if self.window is None:
            wpos, page_ids, offs = paged_write_targets(table, pos, t, ps,
                                                       n_valid)
            rows = lambda leaf: leaf[table]
            k_pos = jnp.broadcast_to(
                jnp.arange(table.shape[1] * ps)[None],
                (S, table.shape[1] * ps))
        else:
            R = (pool["k"].shape[0] - 1) // S
            span = R * ps
            if span < self.window + t - 1:
                raise ValueError(
                    f"a step of {t} rows a slot over a window of "
                    f"{self.window} needs a ring of "
                    f"{self.window + t - 1} positions; this pool gives "
                    f"a slot {R} pages of {ps}")
            wpos, page_ids, offs, last = ring_write_targets(
                table, pos, t, ps, R, n_valid)
            # slot s owns pages 1 + s R ..: the rings lie side by
            # side behind the scratch page, so no gather
            rows = lambda leaf: leaf[1:]
            # the position ring row r holds once the step has
            # written: the latest p <= last with p = r (mod span);
            # negative where the slot has not come that far
            k_pos = last[:, None] - (last[:, None]
                                     - jnp.arange(span)[None]) % span
        q, k, v = self._project(params, x, wpos)
        # a value head narrower than the pool keeps it (zeros behind)
        dv, lanes = self.v_head_dim, pool["v"].shape[-1] // K
        if lanes != dv:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, lanes - dv)))
        k_pool = pool["k"].at[page_ids, offs].set(
            k.reshape(S, t, -1).astype(pool["k"].dtype))
        v_pool = pool["v"].at[page_ids, offs].set(
            v.reshape(S, t, -1).astype(pool["v"].dtype))
        if self._by_table(lanes, ps, t, k_pool.dtype):
            from deeplearning4j_tpu.ops.paged_attention import \
                pallas_paged_attention_grouped
            lengths = pos + (t if n_valid is None else n_valid)
            if self.softmax_scale is not None:
                # the kernel scales by dq ** -0.5: the rest goes into
                # q (exact where the ratio is a power of two)
                q = q * (self.softmax_scale * self.qk_head_dim ** 0.5)
            with jax.named_scope("paged_attention/pallas"):
                o = pallas_paged_attention_grouped(
                    q, k_pool, v_pool, table, lengths, pos,
                    n_heads=self.n_heads, n_kv_heads=K)
            if lanes != dv:
                o = o.reshape(S, t, self.n_heads, lanes)[..., :dv] \
                    .reshape(S, t, -1)
            return self._out(params, o, x), {"k": k_pool, "v": v_pool}
        n = k_pos.shape[1]
        keys = rows(k_pool).reshape(S, n, K, self.qk_head_dim)
        values = rows(v_pool).reshape(S, n, K, lanes)
        if lanes != dv:
            values = values[..., :dv]
        out = self._out(params, self._attend(params, q, keys, values,
                                             wpos, k_pos), x)
        return out, {"k": k_pool, "v": v_pool}


@register_layer
@dataclasses.dataclass
class TransformerEncoderLayer(MixerCacheLayer, BaseLayer):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x))."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_heads: int = 4
    ffn_multiplier: int = 4
    causal: bool = False
    activation: str = "gelu"

    # LN + residual + per-token MLP are pointwise in time; the inner
    # attention routes itself through the ring (seq_context)
    seq_parallelizable = True

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size
        if self.n_out is None:
            self.n_out = self.n_in

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        if self.n_in != self.n_out:
            raise ValueError("TransformerEncoderLayer requires "
                             "n_in == n_out (residual)")
        ka, k1, k2 = jax.random.split(key, 3)
        pd = dtypes.policy().param_dtype
        d = self.n_out
        dff = d * self.ffn_multiplier
        attn_p, _ = self._ensure_attn().initialize(
            ka, InputType.recurrent(d))
        p = {
            "attn": attn_p,
            "ln1_g": jnp.ones((d,), pd), "ln1_b": jnp.zeros((d,), pd),
            "ln2_g": jnp.ones((d,), pd), "ln2_b": jnp.zeros((d,), pd),
            "W1": self._sample_w(k1, (d, dff), d, dff),
            "b1": jnp.zeros((dff,), pd),
            "W2": self._sample_w(k2, (dff, d), dff, d),
            "b2": jnp.zeros((d,), pd),
        }
        return p, {}

    def _ensure_attn(self):
        if not hasattr(self, "_attn"):
            self._attn = SelfAttentionLayer(
                n_in=self.n_in, n_out=self.n_out, n_heads=self.n_heads,
                causal=self.causal, weight_init=self.weight_init)
        return self._attn

    _mixer = _ensure_attn

    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        self._ensure_attn()
        # the halves' names on their device ops (metadata only): a
        # profiler trace then splits the block's fusion time
        with jax.named_scope("ln1"):
            h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        with jax.named_scope("attn"):
            a, _ = self._attn.apply(params["attn"], {}, h,
                                    training=training, rng=rng,
                                    mask=mask)
        x = x + a
        return x + self._mlp_half(params, x), state

    def _mlp_half(self, params, x):
        """Pre-LN MLP residual branch — shared by apply and
        apply_stream (per-token, so streaming needs no carry)."""
        with jax.named_scope("ln2"):
            h = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        act = self.activation_fn()
        with jax.named_scope("mlp"):
            return act(h @ params["W1"] + params["b1"]) \
                @ params["W2"] + params["b2"]

    def apply_stream(self, params, cache, x):
        """Incremental decode through the full pre-LN block: the
        inner attention carries the KV cache, the LN/MLP halves are
        per-token (see SelfAttentionLayer.apply_stream)."""
        self._ensure_attn()
        h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        a, cache = self._attn.apply_stream(params["attn"], cache, h)
        x = x + a
        return x + self._mlp_half(params, x), cache

    def zero_stream_cache(self, batch: int, capacity: int, dtype):
        return self._ensure_attn().zero_stream_cache(batch, capacity,
                                                     dtype)

    def apply_stream_bounded(self, params, cache, x, pos):
        """Jittable bounded-cache decode step through the pre-LN
        block (see SelfAttentionLayer.apply_stream_bounded)."""
        self._ensure_attn()
        h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        a, cache = self._attn.apply_stream_bounded(params["attn"],
                                                   cache, h, pos)
        x = x + a
        return x + self._mlp_half(params, x), cache

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """Paged-cache decode step through the pre-LN block (see
        SelfAttentionLayer.apply_stream_paged)."""
        self._ensure_attn()
        # the halves' names as ``apply`` gives them (metadata only)
        with jax.named_scope("ln1"):
            h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        with jax.named_scope("attn"):
            a, pool = self._attn.apply_stream_paged(
                params["attn"], pool, table, pos, h, n_valid)
        x = x + a
        return x + self._mlp_half(params, x), pool


def _stream_attention(q, k_full, v_full, n_cached: int):
    """Exact attention of the NEW chunk's queries over the full
    cached+new history, causal within the chunk: new position i
    (global n_cached + i) sees keys [0, n_cached + i]."""
    from deeplearning4j_tpu.ops.attention import _NEG_INF
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_full) * scale
    t_new = q.shape[1]
    k_pos = jnp.arange(k_full.shape[1])[None, :]
    q_pos = n_cached + jnp.arange(t_new)[:, None]
    logits = jnp.where((k_pos <= q_pos)[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_full)
