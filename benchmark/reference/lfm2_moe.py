"""Plain reference for the ``lfm2_24b_a2b`` configuration.

LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B config.json, ``lfm2_moe``) as a
straightforward ``jax.numpy`` forward pass of ONE row of ids: float32,
matmuls under ``jax.default_matmul_precision("highest")``, no kernels,
no cache, no batching, the short convolution position by position,
every expert applied to every token and masked. It imports nothing of
the program and is given the weights the BENCHMARK made from the seed
(harness/weights.py), in the tree the configuration's builder
declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm2_gain",
       "conv": {"W_in": (D, 3D), "conv_w": (K, D), "W_out": (D, D)},
       "Wg","Wu","Wd"}                                ``conv``, dense
      {... "conv": ..., "moe": {"Wr": (D, E), "br": (E,),
                                "Wg","Wu": (E, D, W), "Wd": (E, W, D)}},
      {... "attn": {"Wq": (D, H*64), "Wk","Wv": (D, Hk*64),
                    "Wo": (H*64, D), "q_norm_gain","k_norm_gain":
                    (64,)}, "moe": ...},              ``full_attention``
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 head (untied)

The ``num_hidden_layers`` layers are the published layers from
``first_layer`` (0 where the file has no such key): published layer
``l`` is of the kind ``layer_types[l]`` and carries the dense MLP
where ``l < num_dense_layers``, the experts elsewhere. Equations
(eps ``norm_eps``; no bias but the router's; ``rms`` is RMSNorm with
its own gain):

  model    h0 = E[ids];  logits = rms(h_last) Wh   (the source calls
           the final norm ``embedding_norm``)
  block    h = x + Mixer(rms(x));  y = h + F(rms(h))
  F dense  (silu(z Wg) * z Wu) Wd   (``intermediate_size``)
  experts  s = sigmoid(z Wr) over ``num_experts``; T = the
           ``num_experts_per_tok`` largest of s + br (the bias enters
           the selection only: ``use_expert_bias``); w_e = s_e /
           (sum_T s + 1e-6) (``norm_topk_prob``) *
           ``routed_scaling_factor``;
           F(z) = sum_{e in T} w_e (silu(z Wg_e) * z Wu_e) Wd_e
           (``moe_intermediate_size``); no shared expert
  attn     ``num_attention_heads`` query heads over
           ``num_key_value_heads`` key/value heads of hidden / heads;
           q = rms_head(n Wq), k = rms_head(n Wk): a norm over each
           head's values, one gain for all query heads and one for
           all key heads; THEN rotary over the whole head (pairs
           (x[i], x[i + d/2]) rotated by pos * theta^(-2i/d), theta
           ``rope_parameters.rope_theta``, no scaling); v = n Wv;
           s_ij = q_i . k_j / sqrt(d) over j <= i; softmax; Wo
  conv     [B | C | x] = n W_in, three chunks of D in that order;
           u_t = B_t * x_t;  c_t = sum_k w[k] * u_{t-K+1+k}, zeros
           before position 0, K ``conv_L_cache``, no bias
           (``conv_bias`` false), no activation;
           o_t = (C_t * c_t) W_out

One matrix group (a mixer's projections, an MLP, one expert inside a
scan over a layer's experts, the head) is widened to float32 at a
time, each layer's part in its own jitted call that takes the stored
weights; the embedding is widened row by row of the ids.

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


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


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


def _rope(x, theta):
    """x (T, N, d): every head rotated in half-split pairs; position
    = row index."""
    T, d = x.shape[0], x.shape[-1]
    freqs = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(T, dtype=np.float64)[:, None] * freqs).astype(
        np.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1)


@_jit(3)
def _conv(p, gain, h, eps, control):
    """h + Conv(rms(h)) for h (T, D), one position at a time."""
    p, D = _up(p, control), h.shape[1]
    proj = _rms(h, _up(gain, control), eps) @ p["W_in"]
    u = proj[:, :D] * proj[:, 2 * D:]

    def position(before, u_t):
        seen = jnp.concatenate([before, u_t[None]])      # oldest first
        return seen[1:], jnp.sum(p["conv_w"] * seen, axis=0)

    K = p["conv_w"].shape[0]
    _, c = jax.lax.scan(position, jnp.zeros((K - 1, D), F32), u)
    return h + (proj[:, D:2 * D] * c) @ p["W_out"]


@_jit(3)
def _attention(p, gain, h, heads, theta, eps, control):
    """h + Attn(rms(h)) for h (T, D)."""
    Hq, Hk = heads
    p, T = _up(p, control), h.shape[0]
    n = _rms(h, _up(gain, control), eps)
    q = _rope(_rms((n @ p["Wq"]).reshape(T, Hq, -1), p["q_norm_gain"],
                   eps), theta)
    k = _rope(_rms((n @ p["Wk"]).reshape(T, Hk, -1), p["k_norm_gain"],
                   eps), theta)
    v = (n @ p["Wv"]).reshape(T, Hk, -1)
    s = jnp.einsum("tkgd,nkd->kgtn", q.reshape(T, Hk, Hq // Hk, -1),
                   k) * q.shape[-1] ** -0.5
    seen = np.arange(T)[None, :] <= np.arange(T)[:, None]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("kgtn,nkd->tkgd", jax.nn.softmax(s, axis=-1), v)
    return h + o.reshape(T, -1) @ p["Wo"]


@_jit(3)
def _mlp(p, gain, h, eps, control):
    """h + MLP(rms(h))."""
    z = _rms(h, _up(gain, control), eps)
    p = _up(p, control)
    return h + _swiglu(z, p["Wg"], p["Wu"], p["Wd"])


@_jit(2)
def _norm(gain, h, eps, control):
    return _rms(h, _up(gain, control), eps)


@_jit(3)
def _route(wr, br, z, k, factor, control):
    """(ids (T, k), weights (T, k)) of the router for normed z."""
    s = jax.nn.sigmoid(z @ _up(wr, control))
    ids = jnp.argsort(-(s + _up(br, control)), axis=-1)[:, :k]
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * factor


@_jit(6)
def _weighted(wg, wu, wd, z, ids, w, control):
    """sum_e w_e E_e(z): every expert applied to every token, one
    expert widened to float32 at a time, its part 0 where it was not
    selected."""
    def one(out, expert):
        e, g, u, d = expert
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        return out + w_e[:, None] * _swiglu(
            z, _up(g, control), _up(u, control), _up(d, control)), None

    return jax.lax.scan(one, jnp.zeros_like(z),
                        (jnp.arange(wg.shape[0]), wg, wu, wd))[0]


def experts(p, z, c, control=False):
    """F(z) of an expert layer for normed z (T, D)."""
    ids, w = _route(p["Wr"], p["br"], z, c["num_experts_per_tok"],
                    float(c["routed_scaling_factor"]), control)
    return _weighted(p["Wg"], p["Wu"], p["Wd"], z, ids, w, bool(control))


@_jit(3)
def _head(norm, head, x, eps, control):
    norm, head = _up(norm, control), _up(head, control)
    return _rms(x, norm["gain"], eps) @ head["W"]


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    c, control = config, bool(control)
    if c["conv_bias"] or not (c["use_expert_bias"]
                              and c["norm_topk_prob"]):
        raise ValueError("the equations above have no convolution "
                         "bias, a selection bias and normalised "
                         "weights")
    ids, eps = jnp.asarray(ids, jnp.int32), c["norm_eps"]
    x = _up(params[0]["W"][ids], control)
    for l, p in enumerate(params[1:-2], c.get("first_layer", 0)):
        kind = c["layer_types"][l]
        if ("conv" in p) != (kind == "conv") or \
                ("moe" in p) != (l >= c["num_dense_layers"]):
            raise ValueError(f"published layer {l}: the weights "
                             "disagree with layer_types or "
                             "num_dense_layers")
        if kind == "conv":
            x = _conv(p["conv"], p["norm1_gain"], x, eps, control)
        else:
            x = _attention(
                p["attn"], p["norm1_gain"], x,
                (c["num_attention_heads"], c["num_key_value_heads"]),
                float(c["rope_parameters"]["rope_theta"]), eps, control)
        if "moe" in p:
            x = x + experts(p["moe"], _norm(p["norm2_gain"], x, eps,
                                            control), c, control)
        else:
            mlp = {n: p[n] for n in ("Wg", "Wu", "Wd")}
            x = _mlp(mlp, p["norm2_gain"], x, eps, control)
    return _head(params[-2], params[-1], x, eps, control)
