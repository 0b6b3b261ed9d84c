"""Plain reference for the ``mimo_v25_ep16`` configuration.

MiMo-V2.5's language model (XiaomiMiMo/MiMo-V2.5 config.json,
``mimo_v2``) as a straightforward ``jax.numpy`` forward pass of ONE
row of ids: float32, matmuls under
``jax.default_matmul_precision("highest")``, no kernels, no cache, the
sliding window as a mask over the whole sequence, every held expert
applied to every token and masked. It imports nothing of the program
and is given the weights the BENCHMARK made from the seed
(harness/weights.py), in the tree the configuration's builder
declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm2_gain",
       "attn": {"Wq": (D, H*192), "Wk": (D, K*192), "Wv": (D, K*128),
                "Wo": (H*128, D)[, "sink": (H,)]},
       "Wg","Wu","Wd"}                                dense block(s)
      {... "attn": ..., "moe": {"Wr": (D, E), "br": (E,),
                                "Wg","Wu","Wd": (held, ...)}},
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 untied head

Layer ``l`` is a window layer where ``hybrid_layer_pattern[l]`` is 1
and an expert layer where ``moe_layer_freq[l]`` is 1. Equations (eps
``layernorm_epsilon``; no bias but the router's; ``rms`` is RMSNorm
with its own gain):

  block    h = x + Attn(rms(x));  y = h + F(rms(h))
           F(z) = (silu(z Wg) * z Wu) Wd   (``intermediate_size``), or
           the expert layer
  Attn     H = ``num_attention_heads`` query heads of ``head_dim``;
           K key heads of ``head_dim`` and value heads of
           ``v_head_dim``, K = ``num_key_value_heads`` (global) or
           ``swa_num_key_value_heads`` (window); query head i reads
           key/value head i // (H / K)
           q = n Wq, k = n Wk, v = ``attention_value_scale`` * (n Wv)
           RoPE on the first r = int(``partial_rotary_factor`` *
           head_dim) values of every q and k head: pairs
           (x[i], x[i + r/2]) rotated by pos * theta^(-2i/r)
           (half-split pairs: ``assumed``), theta ``rope_theta``
           (global) or ``swa_rope_theta`` (window), no scaling
           s_ij = q_i . k_j / sqrt(head_dim) over j <= i (global) or
           i - ``sliding_window`` < j <= i (window)
           global: p = softmax(s). Window
           (``add_swa_attention_sink_bias``; the global kind has
           ``add_full_attention_sink_bias``): a learned logit b_h a
           query head joins the denominator only,
           p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))
           out = concat_h(sum_j p_ij v_j) Wo
  experts  sig = sigmoid(z Wr) over the router's whole width; T = the
           ``num_experts_per_tok`` largest of sig + br (the
           correction bias enters the selection only, ``topk_method``
           "noaux_tc" with ``n_group`` 1); w_e = sig_e / sum_T sig
           (``norm_topk_prob``) * (``routed_scaling_factor`` or 1);
           F(z) = sum_{e in T, held} w_e E_e(z),
           E_e(z) = (silu(z Wg_e) * z Wu_e) Wd_e; no shared expert.
           The chip holds experts [held_first_expert,
           + n_routed_experts) of ``router_experts``; what the others
           would add is left out.

One MATRIX GROUP (one attention's projections, one key head's scores,
one MLP, one expert) is widened to float32 at a time, each in its own
jitted call that takes the stored weights, and attention runs one key
head (its H / K query heads) at a time, so 1,792 positions fit beside
the stored weights.

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


def _rope(x, r, theta):
    """x (T, N, d): the first ``r`` values of every head rotated in
    half-split pairs; position = row index."""
    T = x.shape[0]
    freqs = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    ang = (np.arange(T, dtype=np.float64)[:, None] * freqs).astype(
        np.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


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


def attention_settings(config, window):
    """What one kind of attention layer reads of the configuration,
    hashable: (H, K, head_dim, v_head_dim, rotary values, theta,
    window or 0, sink, value scale)."""
    swa = "swa_" if window else ""
    hd = config[swa + "head_dim"]
    kv = (config["swa_num_key_value_heads"] if window
          else config["num_key_value_heads"])
    return (config[swa + "num_attention_heads"], kv, hd,
            config[swa + "v_head_dim"],
            int(config["partial_rotary_factor"] * hd),
            float(config["swa_rope_theta" if window else "rope_theta"]),
            int(config["sliding_window"]) if window else 0,
            bool(config["add_swa_attention_sink_bias" if window
                        else "add_full_attention_sink_bias"]),
            float(config["attention_value_scale"]))


@_jit(2)
def _norm(gain, h, eps, control):
    return _rms(h, _up(gain, control), eps)


@_jit(2)
def _qkv(p, x, settings, control):
    """x (T, D) projected: q (T,H,dq) and k (T,K,dq) rotated, v
    (T,K,dv) scaled."""
    H, K, dq, dv, r, theta, _, _, scale = settings
    p = _up({n: p[n] for n in ("Wq", "Wk", "Wv")}, control)
    T = x.shape[0]
    q = _rope((x @ p["Wq"]).reshape(T, H, dq), r, theta)
    k = _rope((x @ p["Wk"]).reshape(T, K, dq), r, theta)
    return q, k, scale * (x @ p["Wv"]).reshape(T, K, dv)


@_jit(4)
def _attend_head(q, k, v, sink, window):
    """One key head: q (T,G,dq) of its query heads over k (T,dq) and
    v (T,dv); ``sink`` (G,) or None. Returns (T,G,dv)."""
    T = q.shape[0]
    s = jnp.einsum("tgd,nd->gtn", q, k) * q.shape[-1] ** -0.5
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    e = jnp.where(seen[None], s, -jnp.inf)
    m = jnp.max(e, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[:, None, None])
    e = jnp.exp(e - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        z = z + jnp.exp(sink[:, None, None] - m)
    return jnp.einsum("gtn,nd->tgd", e / z, v)


@_jit(2)
def _out(wo, o, control):
    return o @ _up(wo, control)


def attention(p, x, config, window, control=False):
    """Attn(x) (T, D) of one layer of the given kind, without its
    norm and residual, one key head at a time."""
    settings = attention_settings(config, window)
    H, K, _, dv, _, _, span, has_sink, _ = settings
    q, k, v = _qkv(p, x, settings, control)
    G = H // K
    sink = _up(p["sink"], control).reshape(K, G) if has_sink else None
    o = [_attend_head(q[:, g * G:(g + 1) * G], k[:, g], v[:, g],
                      None if sink is None else sink[g], span)
         for g in range(K)]
    return _out(p["Wo"], jnp.concatenate(o, axis=1).reshape(
        x.shape[0], H * dv), control)


@_jit(3)
def _mlp(p, gain, h, eps, control):
    """h + MLP(rms(h))."""
    z = _rms(h, _up(gain, control), eps)
    p = _up(p, control)
    return h + _swiglu(z, p["Wg"], p["Wu"], p["Wd"])


@_jit(4)
def _route(wr, br, gain, h, eps, k, factor, control):
    """(rms(h), ids (T, k), weights (T, k)) over the router's whole
    width."""
    z = _rms(h, _up(gain, control), eps)
    sig = jax.nn.sigmoid(z @ _up(wr, control))
    ids = jnp.argsort(-(sig + _up(br, control)), axis=-1)[:, :k]
    w = jnp.take_along_axis(sig, ids, axis=-1)
    return z, ids, w / jnp.sum(w, axis=-1, keepdims=True) * factor


@_jit(6)
def _expert(wg, wu, wd, z, ids, w, e, control):
    """Expert ``e``'s weighted part for every token, 0 where it was
    not selected."""
    w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
    return w_e[:, None] * _swiglu(z, _up(wg, control), _up(wu, control),
                                  _up(wd, control))


def _experts(p, gain, h, c, control=False):
    """(h + F(rms(h)), the selected experts (T, k) ascending)."""
    first, held = c.get("held_first_expert", 0), p["Wg"].shape[0]
    z, ids, w = _route(p["Wr"], p["br"], gain, h, c["layernorm_epsilon"],
                       c["num_experts_per_tok"],
                       float(c["routed_scaling_factor"] or 1.0), control)
    for e in range(held):
        h = h + _expert(p["Wg"][e], p["Wu"][e], p["Wd"][e], z, ids, w,
                        first + e, control)
    return h, jnp.sort(ids, axis=-1)


def _layer(p, x, c, window, control):
    eps = c["layernorm_epsilon"]
    h = x + attention(p["attn"], _norm(p["norm1_gain"], x, eps, control),
                      c, window, control)
    if "moe" in p:
        return _experts(p["moe"], p["norm2_gain"], h, c, control)
    mlp = {n: p[n] for n in ("Wg", "Wu", "Wd")}
    return _mlp(mlp, p["norm2_gain"], h, eps, control), None


@_jit(3)
def _head(norm, head, x, eps, control):
    norm, head = _up(norm, control), _up(head, control)
    return _rms(x, norm["gain"], eps) @ head["W"]


def _forward(params, ids, config, control):
    ids, control = jnp.asarray(ids, jnp.int32), bool(control)
    x = _up(params[0]["W"][ids], control)
    chosen = []
    for l, p in enumerate(params[1:-2]):
        if ("moe" in p) != bool(config["moe_layer_freq"][l]):
            raise ValueError(f"layer {l}: the weights and "
                             "moe_layer_freq disagree on its kind")
        x, sel = _layer(p, x, config,
                        bool(config["hybrid_layer_pattern"][l]), control)
        chosen.append(sel)
    z = _head(params[-2], params[-1], x, config["layernorm_epsilon"],
              control)
    return z, chosen


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    return _forward(params, ids, config, control)[0]


def selected_experts(params, ids, config):
    """Per layer, the (T, k) experts of the router's whole width that
    the float32 router selects, ascending; None for a dense layer."""
    return _forward(params, ids, config, False)[1]
