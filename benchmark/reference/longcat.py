"""Plain reference for the ``longcat_ep32`` configuration.

LongCat-Flash-Omni's language model (meituan-longcat/LongCat-Flash-Omni
config.json; the layer is LongCat-Flash's shortcut-connected expert
block, arXiv 2509.01322) as a straightforward ``jax.numpy`` forward
pass of ONE row of ids: float32, matmuls under
``jax.default_matmul_precision("highest")``, no kernels, no cache,
every key and value rebuilt from the latent (the unabsorbed
attention), every held expert applied to every token and masked, a
zero-compute expert as ``w z``. It imports nothing of the program and
is given the weights the BENCHMARK made from the seed
(harness/weights.py), in the tree the configuration's builder
declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm_a0_gain","norm_f0_gain","norm_a1_gain","norm_f1_gain",
       "attn0","attn1": {"Wqa","q_gain","Wqb","Wkva","kv_gain",
                         "Wkvb","Wo"},
       "mlp0","mlp1": {"Wg","Wu","Wd"},
       "moe": {"Wr": (D, E + Z), "br": (E + Z,),
               "Wg","Wu","Wd": (held, ...)}},         one per layer
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 untied head

Equations (eps ``rms_norm_eps``; no bias but the router's; ``rms`` is
RMSNorm with its own gain wherever it stands):

  layer    h0 = x  + MLA_0(rms(x));  z0 = rms(h0)
           m  = MoE(z0)
           h1 = h0 + MLP_0(z0)
           h2 = h1 + MLA_1(rms(h1))
           y  = h2 + MLP_1(rms(h2)) + m
           MLP_i(z) = (silu(z Wg_i) * z Wu_i) Wd_i
  MLA      s_q = sqrt(d / q_lora_rank), s_kv = sqrt(d / kv_lora_rank)
           (``mla_scale_q_lora`` / ``mla_scale_kv_lora``)
           q = (rms(x Wqa) Wqb) * s_q -> heads x (nope | rope)
           [c | k_r] = x Wkva; c = rms(c) * s_kv; k_r = RoPE(k_r),
           one rotary key shared by all heads, NOT scaled
           [k_nope | v] = c Wkvb -> heads x (nope | v)
           score = (q_nope . k_nope + RoPE(q_rope) . k_r)
                   * (nope + rope)^-0.5, causal softmax,
           out = concat_heads(P v) Wo
  RoPE     pairs (x[2i], x[2i+1]) rotated by pos * theta^(-2i/rope)
           (interleaved: ``assumed``), no scaling
  experts  p = softmax(z Wr) over the E routed + Z zero experts;
           T = the ``moe_topk`` largest of p + br (the bias enters
           the selection only); w_e = routed_scaling_factor * p_e,
           no normaliser over T;
           MoE(z) = sum_{e in T, e < E, held} w_e E_e(z)
                    + sum_{e in T, e >= E} w_e z,
           E_e(z) = (silu(z Wg_e) * z Wu_e) Wd_e. The chip holds
           experts [held_first_expert, + n_routed_experts) of the
           ``router_experts`` routed ones; what the others would add
           is left out.

One MATRIX (one attention, one MLP, one expert) is widened to float32
at a time: each is its own jitted call that takes the stored weights,
so the reference fits beside the stored weights and little else (a
whole layer in float32 is 5 GB).

The CONTROL (``logits(..., control=True)``) is this reference with
every weight rounded to float8_e4m3 (the precision below the
bfloat16 the configuration states) before it is widened; ``correct``
must reject it.
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


def _rope(x, theta):
    """x (T, ..., d) interleaved pairs; position = row index."""
    T, d = x.shape[0], x.shape[-1]
    freqs = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(T, dtype=np.float64)[:, None] * freqs).astype(
        np.float32)
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


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


@_jit(3)
def _mla(p, gain, h, config_items, control):
    """h + MLA(rms(h))."""
    c = dict(config_items)
    p, gain = _up(p, control), _up(gain, control)
    eps, d = c["rms_norm_eps"], h.shape[-1]
    x = _rms(h, gain, eps)
    T = x.shape[0]
    H, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    r = c["kv_lora_rank"]
    s_q = (d / c["q_lora_rank"]) ** 0.5 if c["mla_scale_q_lora"] else 1.0
    s_kv = (d / r) ** 0.5 if c["mla_scale_kv_lora"] else 1.0
    q = (_rms(x @ p["Wqa"], p["q_gain"], eps) @ p["Wqb"]) * s_q
    q = q.reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], c["rope_theta"])
    kv = x @ p["Wkva"]
    ckv = _rms(kv[:, :r], p["kv_gain"], eps) * s_kv
    k_r = _rope(kv[:, r:], c["rope_theta"])                  # (T, dr)
    kvb = (ckv @ p["Wkvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("thd,khd->htk", q_nope, k_nope)
         + jnp.einsum("thd,kd->htk", q_rope, k_r)) * (dn + dr) ** -0.5
    s = jnp.where(np.tril(np.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)
    return h + o.reshape(T, H * dv) @ p["Wo"]


@_jit(3)
def _mlp(p, gain, h, eps, control):
    """(rms(h), MLP(rms(h)))."""
    z = _rms(h, _up(gain, control), eps)
    p = _up(p, control)
    return z, _swiglu(z, p["Wg"], p["Wu"], p["Wd"])


@_jit(3)
def _route(wr, br, z, k, factor, control):
    """(ids (T, k), weights (T, k)) over the router's whole width."""
    wr, br = _up(wr, control), _up(br, control)
    p = jax.nn.softmax(z @ wr, axis=-1)
    ids = jnp.argsort(-(p + br), axis=-1)[:, :k]
    return ids, jnp.take_along_axis(p, ids, axis=-1) * factor


@_jit(7)
def _expert(wg, wu, wd, z, ids, w, e, control):
    """Expert ``e``'s weighted part for every token, 0 where it was
    not selected."""
    w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
    return w_e[:, None] * _swiglu(z, _up(wg, control), _up(wu, control),
                                  _up(wd, control))


def _experts(p, z, c, control=False):
    """(MoE(z), the selected experts (T, k) in ascending order)."""
    first, held = c.get("held_first_expert", 0), p["Wg"].shape[0]
    routed = p["Wr"].shape[1] - c["zero_expert_num"]
    ids, w = _route(p["Wr"], p["br"], z, c["moe_topk"],
                    float(c["routed_scaling_factor"]), control)
    # the zero-compute experts (``zero_expert_type`` "identity")
    out = jnp.sum(jnp.where(ids >= routed, w, 0.0), axis=-1)[:, None] * z
    for e in range(held):
        out = out + _expert(p["Wg"][e], p["Wu"][e], p["Wd"][e], z, ids,
                            w, first + e, control)
    return out, jnp.sort(ids, axis=-1)


def _layer(p, x, c, static, control):
    eps = c["rms_norm_eps"]
    h = _mla(p["attn0"], p["norm_a0_gain"], x, static, control)
    z, f = _mlp(p["mlp0"], p["norm_f0_gain"], h, eps, control)
    m, ids = _experts(p["moe"], z, c, control)
    h = _mla(p["attn1"], p["norm_a1_gain"], h + f, static, control)
    _, f = _mlp(p["mlp1"], p["norm_f1_gain"], h, eps, control)
    return h + f + m, ids


@_jit(3)
def _head(norm, head, x, eps, control):
    norm, head = _up(norm, control), _up(head, control)
    return _rms(x, norm["gain"], eps) @ head["W"]


def _static(config):
    """What the attention reads of the configuration, hashable."""
    if config.get("zero_expert_type", "identity") != "identity":
        raise ValueError("zero_expert_type "
                         f"{config['zero_expert_type']!r}: the zero "
                         "experts here are identities")
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rms_norm_eps", "mla_scale_q_lora",
            "mla_scale_kv_lora")
    return tuple((k, config[k]) for k in keys)


def _forward(params, ids, config, control):
    ids, control = jnp.asarray(ids, jnp.int32), bool(control)
    x = _up(params[0]["W"][ids], control)
    static, chosen = _static(config), []
    for p in params[1:-2]:
        x, sel = _layer(p, x, config, static, control)
        chosen.append(sel)
    z = _head(params[-2], params[-1], x, config["rms_norm_eps"], control)
    return z, chosen


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    return _forward(params, ids, config, control)[0]


def selected_experts(params, ids, config):
    """Per layer, the (T, k) experts of the router's whole width
    (routed, then zero) that the float32 router selects, in ascending
    order."""
    return _forward(params, ids, config, False)[1]
