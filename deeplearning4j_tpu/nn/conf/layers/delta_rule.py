"""Gated delta-rule linear attention (Yang, Kautz & Hatamizadeh,
"Gated Delta Networks", arXiv 2412.06464; the ``linear_attention``
layer of ``olmo_hybrid`` configs): a recurrence whose state is a
MATRIX a head that is read before it is written, behind three short
causal convolutions, gated and normed.

For one token ``x`` (B, C) of width ``D``, with H heads of ``dk`` key
and ``dv`` value channels and K the convolution's width:

  q~ = x Wq, k~ = x Wk  (H dk);  v~ = x Wv, z = x Wg  (H dv);
  a = x Wa, b = x Wb  (H)
  [q' | k' | v']_t = silu(sum_j w[j] * [q~ | k~ | v~]_{t-K+1+j})
                                     channel by channel, zeros before
                                     position 0, no bias
  q = q' / (|q'|_2 sqrt(dk)),  k = k' / |k'|_2    a head, ``eps``
                                     inside the root;  v = v'
  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))   in (0, 1)
  beta_t = sigmoid(b_t), doubled with ``allow_neg_eigval`` (Grazzi et
           al., arXiv 2411.12537: the transition's eigenvalues then
           lie in (-1, 1))
  S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
  o_t = S_t^T q_t                    S is dk x dv a head, S_{-1} = 0
  y_t = ((rms_head(o_t) * g) * silu(z_t)) Wo   the norm over each
                                     head's dv values with ONE gain
                                     ``g`` of dv, the gate AFTER it

What a stream carries from one token to the next has a FIXED size
whatever its length: ``S`` (H, dk, dv) and the convolutions' last
K - 1 inputs. ``apply`` runs the recurrence over a whole sequence as a
``lax.scan`` over positions (plain and differentiable; the blocked
form for long sequences is not written). ``apply_stream_paged`` is the
serving step over a pool with one row a SLOT, under the contract
``Mamba2MixerLayer.apply_stream_paged`` states.

The state is held ``(.., H / p, dk, p * dv)``: ``p`` heads side by
side on the minor axis, the fewest that make it whole lane tiles
(``_pack``: two heads of 192 are three tiles of 128; a float32
``(.., 96, 192)`` would be padded to 256 on the device, a third more
bytes held and moved). Every per-head value is spread over its head's
``dv`` lanes by a select (``_spread``), which fuses into the pass over
the state; nothing of the state's size is ever transposed.

The serving step's write needs the result of its reads, so in
``jax.numpy`` the pool crosses the memory three times a step (XLA makes
one fusion of the reads and a second, which reads the pool again, of
the write). On a TPU, for the shapes ``ops.delta_state
.delta_state_pass`` admits, the step between the convolutions and the
gated norm is one kernel (``pallas_delta_state``) that holds a slot's
tile in fast memory between the reads and the write: the pool is read
once and written once. The ``jax.numpy`` form is that kernel's oracle
and the path everywhere else (the CPU, a ``p`` with no whole lane
tile, ``apply``, ``fit``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.dtypes import einsum_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (BaseLayer,
                                                    register_layer)
from deeplearning4j_tpu.nn.conf.layers.paged import (STATE, PagedCache,
                                                     PagedLayer)
from deeplearning4j_tpu.nn.conf.layers.state_space import carried_window
from deeplearning4j_tpu.ops import delta_state

__all__ = ["GatedDeltaMixerLayer"]

_F32 = jnp.float32
# the device's lane tile: the minor axis of the state is a multiple of
# it where some count of heads side by side makes one
_LANES = 128


@register_layer
@dataclasses.dataclass
class GatedDeltaMixerLayer(PagedLayer, BaseLayer):
    """Gated delta-rule mixer, (B,T,C) -> (B,T,C). The fields carry
    the source's meanings: ``n_heads`` H (``linear_num_key_heads`` =
    ``linear_num_value_heads``), ``key_head_dim`` dk, ``value_head_dim``
    dv, ``conv_width`` K (``linear_conv_kernel_dim``),
    ``allow_neg_eigval``, ``eps`` of the L2 norms and of the gated
    norm. No bias.

    Parameters: ``Wq``, ``Wk`` (D, H dk), ``Wv``, ``Wg`` (D, H dv),
    ``Wa``, ``Wb`` (D, H), ``conv_w`` (K, 2 H dk + H dv) (the source's
    three (channels, 1, K) with the channels last, q | k | v),
    ``A_log``, ``dt_bias`` (H,), ``g`` (dv,), ``Wo`` (H dv, D), all in
    the policy's parameter dtype; the convolution's sum, the norms,
    the gates and every product of the recurrence run in float32."""

    n_in: Optional[int] = None
    n_heads: int = 4
    key_head_dim: int = 8
    value_head_dim: int = 16
    conv_width: int = 4
    allow_neg_eigval: bool = False
    eps: float = 1e-6

    def __post_init__(self):
        if self.conv_width < 2:
            raise ValueError(
                f"conv_width must be >= 2, got {self.conv_width}")

    @property
    def key_dim(self) -> int:
        return self.n_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.n_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def _pack(self) -> int:
        """Heads side by side on the state's minor axis: the fewest
        that make it whole lane tiles, 1 where no count of them
        does."""
        for p in range(1, self.n_heads + 1):
            if self.n_heads % p == 0 and \
                    (p * self.value_head_dim) % _LANES == 0:
                return p
        return 1

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in or input_type.size,
                                   input_type.timesteps)

    def initialize(self, key, input_type: InputType):
        """The published implementation's own, a LONG memory: ``A``
        uniform in (0, 16], ``dt`` log-uniform in [0.001, 0.1] through
        the inverse softplus, so ``alpha = exp(-A dt)`` lies in
        (0.2, 1); the gain ones, the convolutions uniform within
        1 / sqrt(K)."""
        self.set_n_in(input_type)
        d, H, K = self.n_in, self.n_heads, self.conv_width
        kd, vd = self.key_dim, self.value_dim
        pd = dtypes.policy().param_dtype
        ks = jax.random.split(key, 10)
        w = lambda k, a, b: self._sample_w(k, (a, b), a, b)
        dt = jnp.exp(jax.random.uniform(
            ks[8], (H,), _F32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "Wq": w(ks[0], d, kd), "Wk": w(ks[1], d, kd),
            "Wv": w(ks[2], d, vd), "Wg": w(ks[3], d, vd),
            "Wa": w(ks[4], d, H), "Wb": w(ks[5], d, H),
            "conv_w": jax.random.uniform(
                ks[6], (K, self.conv_dim), _F32, -K ** -0.5, K ** -0.5
            ).astype(pd),
            "A_log": jnp.log(16.0 - jax.random.uniform(
                ks[7], (H,), _F32, 0.0, 16.0)).astype(pd),
            # softplus(dt_bias) = dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "g": jnp.ones((self.value_head_dim,), pd),
            "Wo": w(ks[9], vd, d),
        }, {}

    # ---- the packed layout: p heads side by side on W = p dv lanes --
    def _spread(self, x, trailing=0):
        """A value a head, ``x`` (.., H, *rest) with ``trailing`` axes
        behind the heads', over its head's lanes: (.., H / p, *rest, W),
        or (.., H, *rest, 1) where ``p`` is 1. Selects, which fuse
        into whatever reads them."""
        p, dv = self._pack, self.value_head_dim
        ax = x.ndim - 1 - trailing
        x = x.reshape(*x.shape[:ax], -1, p, *x.shape[ax + 1:])
        part = lambda j: lax.index_in_dim(x, j, ax + 1,
                                          keepdims=False)[..., None]
        out = part(0)
        lane = np.arange(p * dv) // dv
        for j in range(1, p):
            out = jnp.where(lane == j, part(j), out)
        return out

    def _head_mean(self, y):
        """y (.., H / p, W): at every lane the mean over its own
        head's dv lanes."""
        p, dv = self._pack, self.value_head_dim
        lane = np.arange(p * dv) // dv
        out = 0.0
        for j in range(p):
            own = lane == j
            out = jnp.where(own, jnp.sum(jnp.where(own, y, 0.0), axis=-1,
                                         keepdims=True), out)
        return out / dv

    # ---- pieces shared by both forms ----
    def _in_proj(self, params, x):
        """x (B,t,C) -> u (B,t,conv_dim) = q~ | k~ | v~ in the
        parameters' dtype (what the convolutions' window keeps), and
        in float32 z (B,t,H dv), a and b (B,t,H)."""
        x = x.astype(params["Wq"].dtype)
        proj = lambda name: einsum_f32("btc,cn->btn", x, params[name])
        u = jnp.concatenate([proj("Wq"), proj("Wk"), proj("Wv")],
                            axis=-1).astype(x.dtype)
        return u, proj("Wg"), proj("Wa"), proj("Wb")

    def _conv(self, params, window, u):
        """The causal convolutions of ``u`` (B,t,conv_dim) behind the
        K - 1 inputs before it, ``window`` (B,K-1,conv_dim), and the
        heads' L2 norms: ``(q and k (B,t,H,dk), v (B,t,H / p,W), the
        inputs with their window in front (B,K-1+t,conv_dim))``,
        float32 but the last."""
        t, kd = u.shape[1], self.key_dim
        H, dk = self.n_heads, self.key_head_dim
        xs = jnp.concatenate([window.astype(u.dtype), u], axis=1)
        w = params["conv_w"].astype(_F32)
        acc = w[0] * xs[:, :t].astype(_F32)
        for j in range(1, self.conv_width):
            acc = acc + w[j] * xs[:, j:j + t].astype(_F32)
        c = jax.nn.silu(acc)
        lead = c.shape[:2]
        unit = lambda y: y * lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + self.eps)
        return (unit(c[..., :kd].reshape(*lead, H, dk)) * dk ** -0.5,
                unit(c[..., kd:2 * kd].reshape(*lead, H, dk)),
                c[..., 2 * kd:].reshape(*lead, H // self._pack, -1), xs)

    def _gates(self, params, a, b):
        """(alpha, beta), (B,t,H) float32: the state's decay and the
        strength of the write."""
        alpha = jnp.exp(-jnp.exp(params["A_log"].astype(_F32))
                        * jax.nn.softplus(
                            a + params["dt_bias"].astype(_F32)))
        beta = jax.nn.sigmoid(b)
        return alpha, 2.0 * beta if self.allow_neg_eigval else beta

    def _gate_norm(self, params, o, z):
        """o (B,t,H / p,W) float32 normed a head, then gated by z
        (B,t,H dv): what ``Wo`` is given, in its dtype."""
        lead = o.shape[:2]
        o = o * lax.rsqrt(self._head_mean(jnp.square(o)) + self.eps)
        o = o * jnp.tile(params["g"].astype(_F32), self._pack)
        o = o.reshape(*lead, -1) * jax.nn.silu(z)
        return o.astype(params["Wo"].dtype)

    def _zero_state(self, n: int):
        p = self._pack
        return jnp.zeros((n, self.n_heads // p, self.key_head_dim,
                          p * self.value_head_dim), _F32)

    # ---- full sequence ----
    def apply(self, params, state, x, *, training=False, rng=None,
              mask=None):
        if mask is not None:
            raise NotImplementedError(
                "GatedDeltaMixerLayer has no padding mask: feed "
                "sequences of one length")
        x = self.apply_input_dropout(x, training=training, rng=rng)
        B = x.shape[0]
        u, z, a, b = self._in_proj(params, x)
        q, k, v, _ = self._conv(
            params, jnp.zeros((B, self.conv_width - 1, self.conv_dim),
                              u.dtype), u)
        alpha, beta = self._gates(params, a, b)

        def one(S, row):
            q_t, k_t, v_t, al, be = row
            kx = self._spread(k_t, 1)
            S = self._spread(al)[:, :, None] * S
            u_t = self._spread(be) * (v_t - jnp.sum(S * kx, axis=-2))
            S = S + kx * u_t[:, :, None]
            return S, jnp.sum(S * self._spread(q_t, 1), axis=-2)

        _, o = lax.scan(one, self._zero_state(B), tuple(
            jnp.moveaxis(m, 1, 0) for m in (q, k, v, alpha, beta)))
        return self._gate_norm(params, jnp.moveaxis(o, 0, 1), z) \
            @ params["Wo"], state

    # ---- the serving step: a pool with one row a slot ----
    def paged_cache(self, page_size: int) -> PagedCache:
        """A row a slot. The paged step solves a chunk's t rows by
        forward substitution, unrolled and quadratic in t
        (``apply_stream_paged``), so the batcher keeps such a network
        to one chunk width, as the Mamba-2 mixer's."""
        return PagedCache(STATE, unrolls_chunk_rows=True)

    def zero_pool(self, slots: int, page_size: int, dtype):
        """{'state': (slots, H / p, dk, p dv) float32, 'conv': (slots,
        K - 1, conv_dim) ``dtype``}: row ``s`` belongs to slot ``s``
        (``Mamba2MixerLayer.zero_pool``)."""
        return {"state": self._zero_state(slots),
                "conv": jnp.zeros((slots, self.conv_width - 1,
                                   self.conv_dim), dtype)}

    def apply_stream_paged(self, params, pool, table, pos, x,
                           n_valid=None):
        """One step for all slots, the contract of
        ``Mamba2MixerLayer.apply_stream_paged``: row ``s`` of ``x``
        (S,t,C) is slot ``s``, which feeds its first ``n_valid[s]``
        rows in order; a slot whose ``pos`` is 0 starts from zeros by
        POSITION whatever its row holds; a slot that feeds no row (the
        all-zero table row in the single-row program) keeps its state
        and its window. Returns (out, pool).

        The t rows are coupled (row i reads what rows j < i wrote for
        ``k_i``), so they are solved by forward substitution and the
        state is read and written once a step whatever t: with ``G_i =
        alpha_1 .. alpha_i`` and ``G_i / G_j`` the product of the
        alphas between,

          u_i = beta_i (v_i - G_i S_0^T k_i
                        - sum_{j<i} (G_i / G_j)(k_j . k_i) u_j)
          o_i = G_i S_0^T q_i + sum_{j<=i} (G_i / G_j)(k_j . q_i) u_j
          S_t = G_t S_0 + sum_j (G_t / G_j) k_j u_j^T

        A row past ``n_valid`` has ``alpha = 1``, ``beta = 0`` and
        changes nothing. Every product is elementwise in float32.
        Where ``delta_state_pass`` admits the pool (a TPU, a float32
        state of whole lane tiles) the three lines are one kernel
        over a tile held in fast memory, the pool read once and
        written once; elsewhere they are ``jax.numpy``: the 2 t
        reductions over ``S_0`` are each over the state's own shape,
        so that XLA fuses them into one pass over the pool, and the
        write, which needs their result, is a second."""
        S, t, _ = x.shape
        if n_valid is None:
            n_valid = jnp.where(table[:, 0] > 0, t, 0)
        fed, fresh = n_valid > 0, pos == 0
        state = pool["state"]                           # (S,H/p,dk,W)
        # a fresh slot's row is masked where it is USED (a select, so
        # that whatever the row holds, even a non-finite value, is
        # dropped), as the Mamba-2 mixer's
        restart = fresh[:, None, None]
        window = jnp.where(restart, 0, pool["conv"])
        u, z, a, b = self._in_proj(params, x)
        with jax.named_scope("state"):
            q, k, v, xs = self._conv(params, window, u)
            valid = (jnp.arange(t)[None, :] < n_valid[:, None])[..., None]
            alpha, beta = self._gates(params, a, b)
            # the window the next step finds: the K - 1 inputs before
            # row n_valid, its own where the slot fed nothing
            after = lambda new: {"state": new, "conv": carried_window(
                pool["conv"], xs, n_valid)}
            if delta_state.delta_state_pass(*state.shape, t, state.dtype):
                masked = lambda x, off: jnp.where(valid, x, off)
                # the rows' products a head, (S,t,t,H) at [:, j, i]
                pairs = lambda m: jnp.sum(
                    k[:, :, None] * m[:, None], axis=-1)
                o, new = delta_state.pallas_delta_state(
                    state, k, q, v, masked(alpha, 1.0), masked(beta, 0.0),
                    pairs(k), pairs(q), fresh, fed)
                pool = after(new)
            else:
                al = self._spread(jnp.where(valid, alpha, 1.0))  # (S,t,H/p,W)
                be = self._spread(jnp.where(valid, beta, 0.0))
                kx = [self._spread(k[:, i], 1) for i in range(t)]
                read = lambda m: jnp.where(
                    restart, 0.0, jnp.sum(state * m, axis=-2))
                # (k_j . k_i) and (k_j . q_i) of every pair of rows at
                # [:, j, i], over their head's lanes
                pairs = lambda m: self._spread(jnp.sum(
                    k[:, :, None] * m[:, None], axis=-1))   # (S,t,t,H/p,W)
                kk, kq = pairs(k), pairs(q)
                # row by row; what the rows before left is one sum over
                # them. G = G_i and since[:, j] = G_i / G_j, the alphas
                # after row j up to row i
                one = jnp.ones_like(al[:, :1])
                G, since, us, os = 1.0, None, [], []
                for i in range(t):
                    G = G * al[:, i]
                    since = one if since is None else jnp.concatenate(
                        [since * al[:, i, None], one], axis=1)
                    u_i = v[:, i] - G * read(kx[i])
                    if i:
                        u_i = u_i - jnp.sum(since[:, :i] * kk[:, :i, i]
                                            * jnp.stack(us, axis=1), axis=1)
                    us.append(be[:, i] * u_i)
                    os.append(G * read(self._spread(q[:, i], 1)) + jnp.sum(
                        since * kq[:, :i + 1, i] * jnp.stack(us, axis=1),
                        axis=1))
                new = jnp.where(restart[..., None], 0.0,
                                G[:, :, None] * state)
                for j in range(t):
                    new = new + (since[:, j] * us[j])[:, :, None] * kx[j]
                pool = after(jnp.where(fed[:, None, None, None], new,
                                       state))
                o = jnp.stack(os, axis=1)
            y = self._gate_norm(params, o, z)
        return y @ params["Wo"], pool
