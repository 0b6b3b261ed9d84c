"""Plain reference for the ``olmo_hybrid_7b`` configuration.

Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json, ``olmo_hybrid``)
as a straightforward ``jax.numpy`` forward pass of ONE row of ids:
float32, matmuls under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, the delta rule position by position.
It imports nothing of the program and is given the weights the
BENCHMARK made from the seed (harness/weights.py), in the tree the
configuration's builder declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm2_gain","Wg","Wu","Wd",
       "delta": {"Wq","Wk": (D, H dk), "Wv","Wg": (D, H dv),
                 "Wa","Wb": (D, H), "conv_w": (K, 2 H dk + H dv),
                 "A_log","dt_bias": (H,), "g": (dv,),
                 "Wo": (H dv, D)}}                    ``linear_attention``
      {... "attn": {"Wq","Wk","Wv","Wo": (D, D),
                    "q_norm_gain","k_norm_gain": (D,)}},
                                                      ``full_attention``
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 head (untied)

Layer ``l`` is of the kind ``layer_types[l]``. Equations (eps
``rms_norm_eps``; no bias anywhere; ``rms`` is RMSNorm with its own
gain):

  model    h0 = E[ids];  logits = rms(h_last) Wh
  block    h = x + rms(Mixer(x));  y = h + rms(F(h)),
           F(z) = (silu(z Wg) * z Wu) Wd  (``intermediate_size``): the
           mixer and the MLP read the residual stream as it is, each
           branch's OUTPUT is normed (the Olmo family's placement)
  full     q = rms(x Wq), k = rms(x Wk), each over the whole projected
           width with one gain of that width; v = x Wv;
           ``num_attention_heads`` heads of hidden / heads, each query
           head its own key head; no rotary
           (``rope_parameters.rope_theta`` null);
           s_ij = q_i . k_j / sqrt(head) over j <= i; softmax; Wo
  linear   H ``linear_num_value_heads`` (= ``linear_num_key_heads``),
           dk ``linear_key_head_dim``, dv ``linear_value_head_dim``,
           K ``linear_conv_kernel_dim``
           q~ = x Wq, k~ = x Wk, v~ = x Wv, z = x Wg, a = x Wa,
           b = x Wb
           [q' | k' | v']_t = silu(sum_j w[j] [q~ | k~ | v~]_{t-K+1+j}),
           channel by channel, zeros before position 0
           q = q' / (sqrt(sum q'^2 + eps) sqrt(dk)),
           k = k' / sqrt(sum k'^2 + eps)  a head;  v = v'
           alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias)),
           beta_t = sigmoid(b_t), times 2 with
           ``linear_allow_neg_eigval``
           S_t = alpha_t S_{t-1}
                 + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,
           S (dk x dv) a head from zeros;  o_t = S_t^T q_t
           y_t = ((rms_head(o_t) * g) * silu(z_t)) Wo: the norm over
           each head's dv values, one gain of dv, the gate after it

One matrix group (a mixer's projections, an MLP, the head) is widened
to float32 at a time, each in its own jitted call that takes the
stored weights; the embedding is widened row by row of the ids.

The CONTROL (``logits(..., control=True)``) is this reference with
every weight rounded to float8_e4m3 (the precision below the bfloat16
the configuration states) before it is widened; ``correct`` must
reject it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _up(tree, control):
    """The stored weights in float32; the control rounds them to
    float8_e4m3 first."""
    def one(w):
        if control:
            w = w.astype(jnp.float8_e4m3fn)
        return w.astype(F32)
    return jax.tree_util.tree_map(one, tree)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * gain


def _jit(static):
    """jit under ``highest`` matmul precision; the arguments from
    ``static`` on are hashable settings."""
    def wrap(f):
        @functools.wraps(f)
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return f(*args)
        return jax.jit(run, static_argnums=tuple(
            range(static, f.__code__.co_argcount)))
    return wrap


def linear_settings(c):
    """(H, dk, dv, K, allow_neg_eigval) of a linear layer, hashable."""
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("a key head a value head: linear_num_key_heads "
                         "is not linear_num_value_heads")
    return (c["linear_num_value_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"], c["linear_conv_kernel_dim"],
            bool(c["linear_allow_neg_eigval"]))


@_jit(3)
def _linear(p, gain, h, settings, eps, control):
    """h + rms(DeltaRule(h)) for h (T, D)."""
    H, dk, dv, K, neg = settings
    p, T = _up(p, control), h.shape[0]
    u = jnp.concatenate([h @ p["Wq"], h @ p["Wk"], h @ p["Wv"]], axis=1)
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    c = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + T]
                        for j in range(K)))
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                  + eps)
    q = unit(c[:, :H * dk].reshape(T, H, dk)) / np.sqrt(dk)
    k = unit(c[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = c[:, 2 * H * dk:].reshape(T, H, dv)
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(h @ p["Wa"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(h @ p["Wb"]) * (2.0 if neg else 1.0)

    def position(S, row):
        q_t, k_t, v_t, a_t, b_t = row
        S = a_t[:, None, None] * S
        read = jnp.sum(S * k_t[:, :, None], axis=1)           # S^T k
        S = S + (b_t[:, None] * (v_t - read))[:, None, :] * k_t[:, :, None]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(position, jnp.zeros((H, dk, dv), F32),
                        (q, k, v, alpha, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["g"]
    y = (o.reshape(T, H * dv) * jax.nn.silu(h @ p["Wg"])) @ p["Wo"]
    return h + _rms(y, _up(gain, control), eps)


@_jit(3)
def _attention(p, gain, h, heads, eps, control):
    """h + rms(Attn(h)) for h (T, D)."""
    p, T = _up(p, control), h.shape[0]
    q = _rms(h @ p["Wq"], p["q_norm_gain"], eps).reshape(T, heads, -1)
    k = _rms(h @ p["Wk"], p["k_norm_gain"], eps).reshape(T, heads, -1)
    v = (h @ p["Wv"]).reshape(T, heads, -1)
    s = jnp.einsum("thd,nhd->htn", q, k) / np.sqrt(q.shape[-1])
    seen = np.arange(T)[None, :] <= np.arange(T)[:, None]
    s = jnp.where(seen[None], s, -jnp.inf)
    o = jnp.einsum("htn,nhd->thd", jax.nn.softmax(s, axis=-1), v)
    return h + _rms(o.reshape(T, -1) @ p["Wo"], _up(gain, control), eps)


@_jit(3)
def _mlp(p, gain, h, eps, control):
    """h + rms(MLP(h))."""
    p = _up(p, control)
    f = (jax.nn.silu(h @ p["Wg"]) * (h @ p["Wu"])) @ p["Wd"]
    return h + _rms(f, _up(gain, control), eps)


@_jit(3)
def _head(norm, head, x, eps, control):
    norm, head = _up(norm, control), _up(head, control)
    return _rms(x, norm["gain"], eps) @ head["W"]


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    c, control = config, bool(control)
    ids = jnp.asarray(ids, jnp.int32)
    eps = c["rms_norm_eps"]
    if c["num_attention_heads"] != c["num_key_value_heads"]:
        raise ValueError("each query head its own key head")
    x = _up(params[0]["W"][ids], control)
    for kind, p in zip(c["layer_types"], params[1:-2]):
        if ("delta" in p) != (kind == "linear_attention"):
            raise ValueError("the weights and layer_types disagree on "
                             "a layer's kind")
        if kind == "linear_attention":
            x = _linear(p["delta"], p["norm1_gain"], x,
                        linear_settings(c), eps, control)
        else:
            x = _attention(p["attn"], p["norm1_gain"], x,
                           c["num_attention_heads"], eps, control)
        mlp = {n: p[n] for n in ("Wg", "Wu", "Wd")}
        x = _mlp(mlp, p["norm2_gain"], x, eps, control)
    return _head(params[-2], params[-1], x, eps, control)
