"""Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434, §2.1).

Queries come through a low-rank bottleneck; keys and values are
rebuilt from ONE shared latent ``c_kv`` a token (``kv_lora_rank``
wide) plus one rotary key ``k_r`` shared by all heads. The cache holds
those two and nothing per head: ``kv_lora_rank + qk_rope_head_dim``
values a token a layer, where multi-head attention holds
``2 * heads * head_dim``.

Two forms of the same attention:

* ``apply`` (full sequence) rebuilds per-head keys and values from
  the latent and attends over them with exact attention (einsum +
  float32 softmax). It does NOT use the Pallas flash kernel: that
  kernel takes q, k and v of one head size, and here q·k is
  ``qk_nope + qk_rope`` wide and v ``v_head_dim``.
* ``apply_stream_paged`` (decode over the paged latent pool) is the
  ABSORBED form: ``W_kvb``'s key half is folded into the query and its
  value half is applied after the weighted sum, so attention runs
  over the cached latent itself and no per-head key or value is ever
  materialised.

Parameters, activations and cache ride the dtype the parameters were
built in; products accumulate in float32 (the MXU's own), and scores,
softmax and norm statistics are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.conf.layers.paged import (PAGES, PagedCache,
                                                     PagedLayer)
from deeplearning4j_tpu.nn.conf.layers.rotary import (rope, yarn_inv_freq,
                                                      yarn_mscale)

__all__ = ["LatentAttentionLayer", "yarn_inv_freq", "yarn_mscale"]


def _mm(a, b):
    """a @ b in a's dtype (the MXU accumulates in float32)."""
    return a @ b.astype(a.dtype)


@register_layer
@dataclasses.dataclass
class LatentAttentionLayer(PagedLayer, BaseLayer):
    """Causal multi-head latent attention, (B,T,C) -> (B,T,C). No
    bias anywhere."""

    n_in: Optional[int] = None
    n_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 4
    v_head_dim: int = 8
    rope_theta: float = 10000.0
    # {"type": "yarn", "factor", "original_max_position_embeddings",
    #  "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} or None
    rope_scaling: Optional[dict] = None
    eps: float = 1e-6
    # LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``:
    # the query times sqrt(n_in / q_lora_rank) and the normed
    # key-value latent times sqrt(n_in / kv_lora_rank), constants
    # that undo what the two bottlenecks take off the variance
    scale_q_lora: bool = False
    scale_kv_lora: bool = False

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        self.set_n_in(input_type)
        d, H = self.n_in, self.n_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        if dr % 2:
            raise ValueError("qk_rope_head_dim must be even, got "
                             f"{dr}")
        ks = jax.random.split(key, 5)
        ones = lambda n: jnp.ones((n,), dtypes.policy().param_dtype)
        w = lambda k, a, b: self._sample_w(k, (a, b), a, b)
        return {"Wqa": w(ks[0], d, rq), "q_gain": ones(rq),
                "Wqb": w(ks[1], rq, H * (dn + dr)),
                "Wkva": w(ks[2], d, rkv + dr), "kv_gain": ones(rkv),
                "Wkvb": w(ks[3], rkv, H * (dn + dv)),
                "Wo": w(ks[4], H * dv, d)}, {}

    # ---- pieces shared by both forms ----
    def _softmax_scale(self):
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        sc = self.rope_scaling
        if sc and sc.get("mscale_all_dim"):
            m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
            s *= m * m
        return s

    def _rope_tables(self):
        sc = self.rope_scaling
        inv = yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta, sc)
        scale = 1.0
        if sc:
            scale = (yarn_mscale(sc["factor"], sc.get("mscale", 1))
                     / yarn_mscale(sc["factor"],
                                   sc.get("mscale_all_dim", 0) or 0))
        return jnp.asarray(inv), scale

    def _project(self, params, x, positions):
        """x (B,t,C) at ``positions`` (B,t) -> q_nope (B,t,H,dn),
        q_rope (B,t,H,dr) rotated, c_kv (B,t,rkv) normed, k_r
        (B,t,dr) rotated: the last two are what the cache holds.
        ``scale_q_lora`` scales both halves of the query,
        ``scale_kv_lora`` the latent and not the rotary key."""
        B, t, _ = x.shape
        H, dn, dr = (self.n_heads, self.qk_nope_head_dim,
                     self.qk_rope_head_dim)
        x = x.astype(params["Wqa"].dtype)
        inv, scale = self._rope_tables()
        cq = rms_norm(_mm(x, params["Wqa"]), params["q_gain"], self.eps)
        if self.scale_q_lora:
            # scaled in float32, rounded once
            q = (einsum_f32("btr,rn->btn", cq, params["Wqb"])
                 * math.sqrt(self.n_in / self.q_lora_rank)).astype(
                     cq.dtype)
        else:
            q = _mm(cq, params["Wqb"])
        q = q.reshape(B, t, H, dn + dr)
        q_rope = rope(q[..., dn:], positions[:, :, None], inv, scale)
        kv = _mm(x, params["Wkva"])
        ckv = kv[..., :self.kv_lora_rank]
        if self.scale_kv_lora:
            # the cache holds the SCALED latent, so the absorbed
            # decode reads it as it reads an unscaled one
            ckv = (rms_norm(ckv.astype(jnp.float32), params["kv_gain"],
                            self.eps)
                   * math.sqrt(self.n_in / self.kv_lora_rank)).astype(
                       kv.dtype)
        else:
            ckv = rms_norm(ckv, params["kv_gain"], self.eps)
        kr = rope(kv[..., self.kv_lora_rank:], positions, inv, scale)
        return q[..., :dn], q_rope, ckv, kr

    def _kvb(self, params):
        """W_kvb as (rkv, H, dn) for keys and (rkv, H, dv) for
        values."""
        w = params["Wkvb"].reshape(self.kv_lora_rank, self.n_heads,
                                   self.qk_nope_head_dim
                                   + self.v_head_dim)
        return w[..., :self.qk_nope_head_dim], \
            w[..., self.qk_nope_head_dim:]

    def _attend(self, params, q_lat, q_rope, ckv, kr, q_pos):
        """The absorbed attention: ``q_lat`` (B,t,H,rkv) and
        ``q_rope`` (B,t,H,dr) over a latent history ``ckv`` (B,K,rkv),
        ``kr`` (B,K,dr); key j is visible to query i iff
        ``j <= q_pos[b, i]``. Returns (B,t,C)."""
        from deeplearning4j_tpu.ops.attention import _NEG_INF
        ckv, kr = ckv.astype(q_lat.dtype), kr.astype(q_lat.dtype)
        s = (einsum_f32("bthr,bkr->bhtk", q_lat, ckv)
             + einsum_f32("bthd,bkd->bhtk", q_rope, kr))
        s = s * self._softmax_scale()
        k_pos = jnp.arange(ckv.shape[1])[None, None, :]
        s = jnp.where((k_pos <= q_pos[:, :, None])[:, None], s,
                      _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
        return self._from_latent(
            params, jnp.einsum("bhtk,bkr->bthr", p, ckv))

    def _from_latent(self, params, o_lat):
        """Each head's weighted sum of the latent, (B,t,H,rkv),
        through ``W_kvb``'s value half and ``Wo``: (B,t,C)."""
        B, t = o_lat.shape[:2]
        _, wv = self._kvb(params)
        o = jnp.einsum("bthr,rhd->bthd", o_lat, wv)
        return _mm(o.reshape(B, t, -1), params["Wo"])

    def _absorb(self, params, q_nope):
        wk, _ = self._kvb(params)
        return jnp.einsum("bthd,rhd->bthr", q_nope, wk)

    # ---- full sequence ----
    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        """Exact causal attention over per-head keys and values
        rebuilt from the latent (the published, unabsorbed form)."""
        if mask is not None:
            raise NotImplementedError(
                "LatentAttentionLayer has no key-padding mask: feed "
                "sequences of one length")
        from deeplearning4j_tpu.ops.attention import _NEG_INF
        x = self.apply_input_dropout(x, training=training, rng=rng)
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q_nope, q_rope, ckv, kr = self._project(params, x, pos)
        wk, wv = self._kvb(params)
        k_nope = jnp.einsum("bkr,rhd->bkhd", ckv, wk)
        v = jnp.einsum("bkr,rhd->bkhd", ckv, wv)
        s = (einsum_f32("bthd,bkhd->bhtk", q_nope, k_nope)
             + einsum_f32("bthd,bkd->bhtk", q_rope, kr))
        s = s * self._softmax_scale()
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(causal[None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhtk,bkhd->bthd", p, v)
        return _mm(o.reshape(B, T, -1), params["Wo"]), state

    def apply_absorbed(self, params, x):
        """The full sequence through the ABSORBED form (what decode
        computes, without a cache): equal to ``apply`` up to
        rounding, which a test holds."""
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q_nope, q_rope, ckv, kr = self._project(params, x, pos)
        return self._attend(params, self._absorb(params, q_nope),
                            q_rope, ckv, kr, pos)

    # ---- paged latent cache ----
    def paged_cache(self, page_size: int) -> PagedCache:
        return PagedCache(PAGES)

    def zero_pool(self, n_pages: int, page_size: int, dtype):
        """The physical pool of this layer: the normed latent and the
        rotated shared key of every cached token, by page. The key's
        row is whole lane tiles, zeros past ``qk_rope_head_dim``: a
        page of narrower rows is one Mosaic will not copy
        (``ops/paged_attention.py``'s latent kernel)."""
        from deeplearning4j_tpu.ops.paged_attention import lane_tiled
        return {"ckv": jnp.zeros((n_pages, page_size,
                                  self.kv_lora_rank), dtype),
                "kr": jnp.zeros((n_pages, page_size,
                                 lane_tiled(self.qk_rope_head_dim)),
                                dtype)}

    def paged_reads_by_table(self, page_size: int, t: int, dtype) -> bool:
        """Will ``apply_stream_paged`` at ``t`` rows a slot read each
        slot's live pages by table (True), or its whole table
        (False)? A predicate of the shapes
        (``ops.paged_attention.latent_reads_by_table``)."""
        from deeplearning4j_tpu.ops.paged_attention import \
            latent_reads_by_table
        return latent_reads_by_table(
            self.n_heads, self.kv_lora_rank, self.qk_rope_head_dim,
            page_size, t, dtype)

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One decode step for all slots over the paged latent pool
        (the ``SelfAttentionLayer.apply_stream_paged`` contract:
        ``x`` (S,t,C), ``table`` (S,P), ``pos`` (S,), ``n_valid``
        (S,) or None): write each slot's new latent and rotary key at
        its (page, offset) and attend in the absorbed form, over the
        pages a slot holds, read by table and no further than its
        length, where ``paged_reads_by_table`` holds
        (``ops/paged_attention.py``'s latent kernel: the same
        mathematics as ``_attend``, which is its oracle), over each
        slot's virtual cache gathered whole elsewhere. Returns
        (out, pool)."""
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            paged_write_targets)
        S, t, _ = x.shape
        ps = pool["ckv"].shape[1]
        dr = self.qk_rope_head_dim
        wpos, page_ids, offs = paged_write_targets(table, pos, t, ps,
                                                   n_valid)
        q_nope, q_rope, ckv, kr = self._project(params, x, wpos)
        ckv_pool = pool["ckv"].at[page_ids, offs].set(
            ckv.astype(pool["ckv"].dtype))
        kr_pool = pool["kr"].at[page_ids, offs].set(jnp.pad(
            kr.astype(pool["kr"].dtype),
            ((0, 0), (0, 0), (0, pool["kr"].shape[2] - dr))))
        new_pool = {"ckv": ckv_pool, "kr": kr_pool}
        q_lat = self._absorb(params, q_nope)
        if self.paged_reads_by_table(ps, t, ckv_pool.dtype):
            from deeplearning4j_tpu.ops.paged_attention import \
                pallas_paged_attention_latent
            lengths = pos + (t if n_valid is None else n_valid)
            with jax.named_scope("paged_attention/pallas"):
                o_lat = pallas_paged_attention_latent(
                    q_lat, q_rope, ckv_pool, kr_pool, table, lengths, pos,
                    scale=self._softmax_scale())
            return self._from_latent(params, o_lat), new_pool
        K = table.shape[1] * ps
        out = self._attend(
            params, q_lat, q_rope, ckv_pool[table].reshape(S, K, -1),
            kr_pool[table].reshape(S, K, -1)[..., :dr], wpos)
        return out, new_pool
