"""Mamba-2's mixer: a selective state-space recurrence behind a short
causal convolution, gated and normed (Dao & Gu 2024, arXiv 2405.21060
section 7; the layer of ``granitemoehybrid`` / ``mamba2`` configs).

For one token ``n`` (B, C) of width ``D``, with ``d_in = H * P``,
``conv_dim = d_in + 2 G N`` and K the convolution's width:

  [z | u | dt_raw] = n W_in          widths d_in | conv_dim | H
  u'_t = silu(sum_k w[k] * u_{t-K+1+k} + b)   channel by channel,
                                     zeros before position 0
  [x | B | C] = u'_t                 widths d_in (H x P) | G N | G N
  dt_t = softplus(dt_raw_t + dt_bias),  A = -exp(A_log)      a head
  S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
  y_t[h] = S_t[h] C_t + D[h] x_t[h]  S is P x N a head, S_{-1} = 0;
                                     head h reads group h // (H / G)
  o_t = rms(y_t * silu(z_t)) W_out   the gate BEFORE the norm, which
                                     runs over a group's d_in / G
                                     channels with gain ``g``

What a stream carries from one token to the next has a FIXED size
whatever its length: ``S`` (H, P, N) and the convolution's last K - 1
inputs. ``apply`` runs the recurrence over a whole sequence as a
``lax.scan`` over positions (plain and differentiable; the chunked
form for long sequences is not written). ``apply_stream_paged`` is
the serving step: it keeps both in a pool with one row a SLOT.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.paged import (STATE, PagedCache,
                                                     PagedLayer)

__all__ = ["Mamba2MixerLayer", "carried_window"]

_F32 = jnp.float32


def carried_window(kept, xs, n_valid):
    """The window a slot-owned short convolution finds at its next
    step: of ``xs`` (S, K - 1 + t, C), a step's t inputs behind the
    K - 1 before them, the K - 1 before row ``n_valid[s]``; ``kept``
    (S, K - 1, C), the pool's own, where the slot fed nothing. One
    select a count of rows: a gather by row is a handful of small
    programs on the device."""
    width = kept.shape[1]
    for n in range(1, xs.shape[1] - width + 1):
        kept = jnp.where((n_valid == n)[:, None, None],
                         xs[:, n:n + width].astype(kept.dtype), kept)
    return kept


@register_layer
@dataclasses.dataclass
class Mamba2MixerLayer(PagedLayer, BaseLayer):
    """Mamba-2 mixer, (B,T,C) -> (B,T,C). The fields carry the
    source's meanings: ``n_heads`` H (``mamba_n_heads``), ``head_dim``
    P (``mamba_d_head``), ``state_size`` N (``mamba_d_state``),
    ``n_groups`` G (``mamba_n_groups``), ``conv_width`` K
    (``mamba_d_conv``), ``eps`` of the gated norm. No bias but the
    convolution's.

    Parameters: ``W_in`` (D, 2 d_in + 2 G N + H), ``conv_w``
    (K, 1, conv_dim) (the source's (conv_dim, 1, K) with the channels
    last, where the device's lanes are), ``conv_b`` (conv_dim,),
    ``A_log``, ``D``, ``dt_bias`` (H,), ``g`` (d_in,), ``W_out``
    (d_in, D), all in the policy's parameter dtype; the recurrence,
    the convolution's sum, the gate and the norm run in float32."""

    n_in: Optional[int] = None
    n_heads: int = 4
    head_dim: int = 8
    state_size: int = 16
    n_groups: int = 1
    conv_width: int = 4
    eps: float = 1e-5

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_groups {self.n_groups}")
        if self.conv_width < 2:
            raise ValueError(
                f"conv_width must be >= 2, got {self.conv_width}")

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        """Mamba-2's own: ``A`` uniform in [1, 16], ``dt`` log-uniform
        in [0.001, 0.1] through the inverse softplus, ``D`` and the
        gain ones, the convolution uniform within 1 / sqrt(K)."""
        self.set_n_in(input_type)
        d, di, cd, H = self.n_in, self.d_inner, self.conv_dim, self.n_heads
        K = self.conv_width
        pd = dtypes.policy().param_dtype
        ks = jax.random.split(key, 5)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (H,), _F32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "W_in": self._sample_w(ks[0], (d, di + cd + H), d,
                                   di + cd + H),
            "conv_w": jax.random.uniform(
                ks[1], (K, 1, cd), _F32, -K ** -0.5, K ** -0.5
            ).astype(pd),
            "conv_b": jnp.zeros((cd,), pd),
            "A_log": jnp.log(jax.random.uniform(
                ks[2], (H,), _F32, 1.0, 16.0)).astype(pd),
            "D": jnp.ones((H,), pd),
            # softplus(dt_bias) = dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "g": jnp.ones((di,), pd),
            "W_out": self._sample_w(ks[4], (di, d), di, d),
        }, {}

    # ---- pieces shared by both forms ----
    def _in_proj(self, params, x):
        """x (B,t,C) -> z (B,t,d_in) float32, u (B,t,conv_dim) in the
        parameters' dtype (what the convolution's window keeps), and
        dt_raw (B,t,H) float32."""
        di, cd = self.d_inner, self.conv_dim
        w = params["W_in"]
        p = einsum_f32("btc,cn->btn", x.astype(w.dtype), w)
        return (p[..., :di], p[..., di:di + cd].astype(w.dtype),
                p[..., di + cd:])

    def _conv(self, params, window, u):
        """The causal convolution of ``u`` (B,t,conv_dim) behind the
        K - 1 inputs before it, ``window`` (B,K-1,conv_dim): ``(x
        (B,t,H,P), B and C (B,t,G,N) a group, the inputs with their
        window in front (B,K-1+t,conv_dim))``, float32 but the last."""
        t, K = u.shape[1], self.conv_width
        H, G, N = self.n_heads, self.n_groups, self.state_size
        xs = jnp.concatenate([window.astype(u.dtype), u], axis=1)
        w = params["conv_w"].astype(_F32)
        acc = params["conv_b"].astype(_F32)
        for k in range(K):
            acc = acc + w[k, 0] * xs[:, k:k + t].astype(_F32)
        a = jax.nn.silu(acc)
        lead, di = a.shape[:2], self.d_inner
        return (a[..., :di].reshape(*lead, H, self.head_dim),
                a[..., di:di + G * N].reshape(*lead, G, N),
                a[..., di + G * N:].reshape(*lead, G, N), xs)

    def _dt(self, params, dt_raw):
        return jax.nn.softplus(dt_raw
                               + params["dt_bias"].astype(_F32))

    def _gate_norm(self, params, y, z):
        """y (B,t,H,P) float32 gated by z (B,t,d_in) and normed a
        group: what ``W_out`` is given, in its dtype."""
        lead, G = y.shape[:2], self.n_groups
        v = (y.reshape(*lead, -1) * jax.nn.silu(z)).reshape(*lead, G, -1)
        v = v * lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                   keepdims=True) + self.eps)
        v = v.reshape(*lead, -1) * params["g"].astype(_F32)
        return v.astype(params["W_out"].dtype)

    # ---- full sequence ----
    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        if mask is not None:
            raise NotImplementedError(
                "Mamba2MixerLayer has no padding mask: feed sequences "
                "of one length")
        x = self.apply_input_dropout(x, training=training, rng=rng)
        B = x.shape[0]
        z, u, dt_raw = self._in_proj(params, x)
        xh, Bg, Cg, _ = self._conv(
            params, jnp.zeros((B, self.conv_width - 1, self.conv_dim),
                              u.dtype), u)
        Bh, Ch = (jnp.repeat(m, self.n_heads // self.n_groups, axis=2)
                  for m in (Bg, Cg))
        dt = self._dt(params, dt_raw)
        A = -jnp.exp(params["A_log"].astype(_F32))

        def one(S, row):
            x_t, b_t, c_t, dt_t = row
            S = (jnp.exp(dt_t * A)[..., None, None] * S
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
            return S, jnp.sum(S * c_t[:, :, None], axis=-1)

        S0 = jnp.zeros((B, self.n_heads, self.head_dim,
                        self.state_size), _F32)
        _, y = lax.scan(one, S0, tuple(
            jnp.moveaxis(m, 1, 0) for m in (xh, Bh, Ch, dt)))
        y = jnp.moveaxis(y, 0, 1) + \
            params["D"].astype(_F32)[:, None] * xh
        return self._gate_norm(params, y, z) @ params["W_out"], state

    # ---- the serving step: a pool with one row a slot ----
    def paged_cache(self, page_size: int) -> PagedCache:
        """A row a slot. The paged step sums a chunk's t rows in
        closed form, unrolled and quadratic in t
        (``apply_stream_paged``): each width of it is seconds of
        compile a layer, so the batcher keeps such a network to one
        chunk width (``PagedSlotSession.unrolls_chunk_rows``)."""
        return PagedCache(STATE, unrolls_chunk_rows=True)

    def zero_pool(self, slots: int, page_size: int, dtype):
        """{'ssm': (slots, H, P, N) float32, 'conv': (slots, K - 1,
        conv_dim) ``dtype``}: row ``s`` belongs to slot ``s`` for as
        long as the session exists."""
        return {"ssm": jnp.zeros((slots, self.n_heads, self.head_dim,
                                  self.state_size), _F32),
                "conv": jnp.zeros((slots, self.conv_width - 1,
                                   self.conv_dim), dtype)}

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One step for all slots (the ``apply_stream_paged`` contract
        of the attention layers): row ``s`` of ``x`` (S,t,C) is slot
        ``s``, which feeds its first ``n_valid[s]`` rows in order. A
        slot whose ``pos`` is 0 starts from zeros whatever its pool
        row holds: the restart is decided by POSITION, so nothing is
        zeroed when a slot changes hands. A slot that feeds no row
        (in the single-row program, which has no ``n_valid``, the
        slot the session marks by an all-zero table row) keeps its
        state as it was. Returns (out, pool).

        The t rows are summed in closed form, so the state is read
        and written once a step whatever t: with ``c_j`` the sum of
        ``dt_i A`` up to row j, ``y_j = exp(c_j) S_0 C_j + sum_{i<=j}
        exp(c_j - c_i) dt_i (C_j . B_i) x_i + D x_j`` and ``S_t =
        exp(c_t) S_0 + sum_i exp(c_t - c_i) dt_i x_i (outer) B_i``; a
        row past ``n_valid`` has ``dt = 0`` and changes nothing. Every
        product is elementwise in float32."""
        S, t, _ = x.shape
        G = self.n_groups
        if n_valid is None:
            n_valid = jnp.where(table[:, 0] > 0, t, 0)
        fed, fresh = n_valid > 0, pos == 0
        # the heads of a group side by side: they share the group's
        # ``B`` and ``C`` rows, which are broadcast where they are
        # used and never repeated a head
        heads = lambda m: m.reshape(S, G, -1, *m.shape[2:])
        state = heads(pool["ssm"])                      # (S,G,H/G,P,N)
        # a fresh slot's row is masked where it is USED (a select, so
        # that whatever the row holds, even a non-finite value, is
        # dropped): a masked copy of the whole state would be written
        # out for its two readers
        restart = fresh[:, None, None, None]
        window = jnp.where(fresh[:, None, None], 0, pool["conv"])
        z, u, dt_raw = self._in_proj(params, x)
        with jax.named_scope("state"):
            xh, Bg, Cg, xs = self._conv(params, window, u)
            valid = jnp.arange(t)[None, :] < n_valid[:, None]
            dt = jnp.where(valid[..., None], self._dt(params, dt_raw),
                           0.0)
            A = -jnp.exp(params["A_log"].astype(_F32))
            D = params["D"].astype(_F32)
            # The rows' small numbers are sums and products of whole
            # arrays, a row at a time, which fuse into the programs
            # that use them: a cumsum, a contraction at the highest
            # precision or a gather by row is each a few programs of
            # its own on the device, a layer
            # (``tests/test_chip_compile.py`` holds that none is left)
            c = [dt[:, 0] * A]                              # t of (S,H)
            for i in range(1, t):
                c.append(c[-1] + dt[:, i] * A)
            since = lambda j: jnp.exp(
                c[j][:, None] - jnp.stack(c[:j + 1], axis=1)
            ) * dt[:, :j + 1]               # exp(c_j - c_i) dt_i, i <= j
            # one reduction a row, each over the state's own shape,
            # so that XLA may fuse them with the state's update below
            # into one pass over the pool
            ys = []
            for j in range(t):
                y = heads(jnp.exp(c[j]))[..., None] * jnp.where(
                    restart, 0.0,
                    jnp.sum(state * Cg[:, j, :, None, None, :], axis=-1))
                # the rows up to j: their part of row j's output
                w = since(j).reshape(S, j + 1, G, -1) * jnp.sum(
                    Cg[:, j, None] * Bg[:, :j + 1], axis=-1, keepdims=True)
                ys.append(y.reshape(xh[:, j].shape) + D[:, None] * xh[:, j]
                          + jnp.sum(w.reshape(S, j + 1, -1, 1)
                                    * xh[:, :j + 1], axis=1))
            # what row i leaves in the state the step ends with
            left = since(t - 1)                             # (S,t,H)
            new = jnp.where(restart[..., None], 0.0,
                            heads(jnp.exp(c[-1]))[..., None, None] * state)
            for i in range(t):
                new = new + heads(left[:, i, :, None] * xh[:, i])[
                    ..., None] * Bg[:, i, :, None, None, :]
            # the window the next step finds: the K - 1 inputs before
            # row n_valid, its own where the slot fed nothing
            tail = carried_window(pool["conv"], xs, n_valid)
            pool = {"ssm": jnp.where(fed[:, None, None, None],
                                     new.reshape(pool["ssm"].shape),
                                     pool["ssm"]),
                    "conv": tail}
            v = self._gate_norm(params, jnp.stack(ys, axis=1), z)
        return v @ params["W_out"], pool
