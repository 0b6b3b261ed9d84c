"""Plain reference for the ``granite_4_0_h_micro`` configuration.

Granite-4.0-H-Micro (ibm-granite/granite-4.0-h-micro config.json,
``granitemoehybrid``) as a straightforward ``jax.numpy`` forward pass
of ONE row of ids: float32, matmuls under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, the state-space recurrence position by position. It imports
nothing of the program and is given the weights the BENCHMARK made
from the seed (harness/weights.py), in the tree the configuration's
builder declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm2_gain","Wg","Wu","Wd",
       "ssm": {"W_in": (D, 2 d_in + 2 G N + H),
               "conv_w": (K, 1, conv_dim), "conv_b": (conv_dim,),
               "A_log","D","dt_bias": (H,), "g": (d_in,),
               "W_out": (d_in, D)}}                   ``mamba`` layers
      {... "attn": {"Wq": (D, Hq*64), "Wk","Wv": (D, Hk*64),
                    "Wo": (Hq*64, D)}},               ``attention``
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 head (untied)

Layer ``l`` is of the kind ``layer_types[l]``. Equations (eps
``rms_norm_eps``; no bias but the convolution's; ``rms`` is RMSNorm
with its own gain):

  model    h0 = ``embedding_multiplier`` * E[ids];
           logits = (rms(h_last) Wh) / ``logits_scaling``
  block    h = x + m Mixer(rms(x));  y = h + m F(rms(h)),
           m = ``residual_multiplier``,
           F(z) = (silu(z Wg) * z Wu) Wd  (``shared_intermediate_size``;
           ``num_local_experts`` is 0, so there is no routed part)
  attn     ``num_attention_heads`` query heads over
           ``num_key_value_heads`` key/value heads of hidden / heads;
           no position encoding (``position_embedding_type`` nope);
           s_ij = (q_i . k_j) * ``attention_multiplier`` over j <= i;
           softmax; concat_h(sum_j p_ij v_j) Wo
  mamba    d_in = ``mamba_expand`` * D = H * P (``mamba_n_heads`` x
           ``mamba_d_head``), G ``mamba_n_groups``, N
           ``mamba_d_state``, K ``mamba_d_conv``, conv_dim = d_in +
           2 G N
           [z | u | dt_raw] = n W_in      widths d_in | conv_dim | H
           u'_t = silu(sum_k w[k] * u_{t-K+1+k} + b), zeros before 0
           [x | B | C] = u'_t             widths d_in | G N | G N
           dt_t = softplus(dt_raw_t + dt_bias); A = -exp(A_log)
           S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h]
                    + dt_t[h] x_t[h] (outer) B_t[group of h]
           y_t[h] = S_t[h] C_t[group of h] + D[h] x_t[h]
           o_t = rms_g(y_t * silu(z_t)) W_out, the gate before the
           norm, the norm over each group's d_in / G channels
           (``time_step_limit`` is the default (0, inf): no clamp;
           ``mamba_chunk_size`` is how the published kernels split
           the same recurrence and is not read)

One matrix group (a mixer's ``W_in``, an MLP, the head) is widened to
float32 at a time, each in its own jitted call that takes the stored
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


def mamba_settings(c):
    """(H, P, N, G, K) of a state-space layer, hashable."""
    if c["mamba_expand"] * c["hidden_size"] != \
            c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size is not "
                         "mamba_n_heads * mamba_d_head")
    return (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_conv"])


@_jit(3)
def _mamba(p, gain, h, settings, eps, m, control):
    """h + m Mamba(rms(h)) for h (T, D)."""
    H, P, N, G, K = settings
    p, T = _up(p, control), h.shape[0]
    di = H * P
    proj = _rms(h, _up(gain, control), eps) @ p["W_in"]
    z, u, dt_raw = (proj[:, :di], proj[:, di:di + di + 2 * G * N],
                    proj[:, di + di + 2 * G * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    conv = p["conv_b"] + sum(p["conv_w"][k, 0] * padded[k:k + T]
                             for k in range(K))
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(T, H, P)
    group = np.arange(H) // (H // G)
    B = conv[:, di:di + G * N].reshape(T, G, N)[:, group]     # (T,H,N)
    C = conv[:, di + G * N:].reshape(T, G, N)[:, group]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])               # (T,H)
    A = -jnp.exp(p["A_log"])

    def position(S, row):
        x_t, b_t, c_t, dt_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(position, jnp.zeros((H, P, N), F32),
                        (x, B, C, dt))
    y = y + p["D"][:, None] * x
    v = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
    v = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return h + m * ((v.reshape(T, di) * p["g"]) @ p["W_out"])


@_jit(3)
def _attention(p, gain, h, heads, scale, eps, m, control):
    """h + m Attn(rms(h)) for h (T, D)."""
    Hq, Hk = heads
    p, T = _up(p, control), h.shape[0]
    n = _rms(h, _up(gain, control), eps)
    q = (n @ p["Wq"]).reshape(T, Hk, Hq // Hk, -1)
    k = (n @ p["Wk"]).reshape(T, Hk, -1)
    v = (n @ p["Wv"]).reshape(T, Hk, -1)
    s = jnp.einsum("tkgd,nkd->kgtn", q, k) * scale
    seen = np.arange(T)[None, :] <= np.arange(T)[:, None]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("kgtn,nkd->tkgd", jax.nn.softmax(s, axis=-1), v)
    return h + m * (o.reshape(T, -1) @ p["Wo"])


@_jit(3)
def _mlp(p, gain, h, eps, m, control):
    """h + m MLP(rms(h))."""
    z = _rms(h, _up(gain, control), eps)
    p = _up(p, control)
    return h + m * ((jax.nn.silu(z @ p["Wg"]) * (z @ p["Wu"])) @ p["Wd"])


@_jit(3)
def _head(norm, head, x, eps, divisor, control):
    norm, head = _up(norm, control), _up(head, control)
    return _rms(x, norm["gain"], eps) @ head["W"] / divisor


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    c, control = config, bool(control)
    ids = jnp.asarray(ids, jnp.int32)
    eps, m = c["rms_norm_eps"], float(c["residual_multiplier"])
    x = float(c["embedding_multiplier"]) * _up(params[0]["W"][ids],
                                               control)
    for kind, p in zip(c["layer_types"], params[1:-2]):
        if ("ssm" in p) != (kind == "mamba"):
            raise ValueError("the weights and layer_types disagree on "
                             "a layer's kind")
        if kind == "mamba":
            x = _mamba(p["ssm"], p["norm1_gain"], x, mamba_settings(c),
                       eps, m, control)
        else:
            x = _attention(
                p["attn"], p["norm1_gain"], x,
                (c["num_attention_heads"], c["num_key_value_heads"]),
                float(c["attention_multiplier"]), eps, m, control)
        mlp = {n: p[n] for n in ("Wg", "Wu", "Wd")}
        x = _mlp(mlp, p["norm2_gain"], x, eps, m, control)
    return _head(params[-2], params[-1], x, eps,
                 float(c["logits_scaling"]), control)
