"""Plain reference for the ``axk1_ep16`` configuration.

A.X-K1 (skt/A.X-K1 config.json: the DeepSeek-V2/V3 block) as a
straightforward ``jax.numpy`` forward pass of ONE row of ids: float32,
matmuls under ``jax.default_matmul_precision("highest")``, no kernels,
no cache, every key and value rebuilt from the latent (the published,
unabsorbed attention), every held expert applied to every token and
masked. It imports nothing of the program and is given the weights the
BENCHMARK made from the seed (harness/weights.py), in the tree the
configuration's builder declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm2_gain",
       "attn": {"Wqa","q_gain","Wqb","Wkva","kv_gain","Wkvb","Wo"},
       "Wg","Wu","Wd"}                                dense block(s)
      {... "attn": ..., "moe": {"Wr","Wg","Wu","Wd",  expert blocks
                                "Wsg","Wsu","Wsd"}},
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 untied head

Equations (eps ``rms_norm_eps``; no bias anywhere):

  block    h = x + MLA(rms(x));  y = h + F(rms(h))
           F = SwiGLU(intermediate_size) in the first
           ``first_k_dense_replace`` blocks, the expert layer after
  MLA      c_q = rms(x Wqa); q = c_q Wqb -> heads x (nope + rope)
           [c_kv | k_r] = x Wkva; c_kv = rms(c_kv); k_r = RoPE(k_r),
           one rotary key shared by all heads
           [k_nope | v] = c_kv Wkvb -> heads x (nope + v)
           score = (q_nope . k_nope + RoPE(q_rope) . k_r) * s, causal
           softmax, out = concat_heads(P v) Wo
  RoPE     pairs (x[2i], x[2i+1]) rotated by pos * f_i (interleaved,
           as the DeepSeek HF code pairs them: ``assumed``); f_i the
           YaRN blend of theta^(-2i/d) and the same over ``factor``
           by the linear ramp between the dimensions of ``beta_fast``
           and ``beta_slow`` rotations over the original context;
           cos/sin scaled by mscale(factor, mscale) /
           mscale(factor, mscale_all_dim);
           s = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2,
           mscale(f, m) = 0.1 m ln f + 1
  experts  p = sigmoid(h Wr) over the router's whole width; T = the
           ``num_experts_per_tok`` largest (``topk_method`` "none":
           no group limit, no bias); w_e = routed_scaling_factor *
           p_e / sum_T p;  F(h) = sum_{e in T, held} w_e E_e(h) +
           E_shared(h),  E(h) = (silu(h Wg) * h Wu) Wd. The chip
           holds experts [held_first_expert, + n_routed_experts) of
           ``router_experts``; what the others would add is left out.

One layer's weights are upcast to float32 at a time (each block is one
jitted call that takes the stored weights), so the reference fits
beside nothing but the stored weights.

The CONTROL (``logits(..., control=True)``) is this reference with
every weight rounded to float8_e4m3 (the precision below the
bfloat16 the configuration states) before the upcast; ``correct``
must reject it.
"""

import functools
import math

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


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(config):
    d, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    sc = config.get("rope_scaling")
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not sc:
        return f, 1.0, 1.0
    orig = sc["original_max_position_embeddings"]
    dim_of = lambda rot: (d * math.log(orig / (rot * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    blend = f / sc["factor"] * ramp + f * (1 - ramp)
    m_all = _mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    return (blend, _mscale(sc["factor"], sc.get("mscale", 1)) / m_all,
            m_all * m_all)


def _rope(x, freqs, scale):
    """x (T, ..., d) interleaved pairs; position = row index."""
    T = x.shape[0]
    ang = (np.arange(T, dtype=np.float64)[:, None] * freqs).astype(
        np.float32)
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _mla(p, x, c):
    T = x.shape[0]
    H, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    r, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    freqs, cs_scale, s_scale = _inv_freq(c)
    q = (_rms(x @ p["Wqa"], p["q_gain"], eps) @ p["Wqb"]).reshape(
        T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], freqs, cs_scale)
    kv = x @ p["Wkva"]
    ckv = _rms(kv[:, :r], p["kv_gain"], eps)
    k_r = _rope(kv[:, r:], freqs, cs_scale)                  # (T, dr)
    kvb = (ckv @ p["Wkvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("thd,khd->htk", q_nope, k_nope)
         + jnp.einsum("thd,kd->htk", q_rope, k_r))
    s = s * ((dn + dr) ** -0.5 * s_scale)
    s = jnp.where(np.tril(np.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, H * dv) @ p["Wo"]


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _experts(p, x, c):
    """(F(x), the selected experts (T, k) in ascending order)."""
    k = c["num_experts_per_tok"]
    first, held = c.get("held_first_expert", 0), p["Wg"].shape[0]
    scores = jax.nn.sigmoid(x @ p["Wr"])
    ids = jnp.argsort(-scores, axis=-1)[:, :k]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if c.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * c["routed_scaling_factor"]
    out = _swiglu(x, p["Wsg"], p["Wsu"], p["Wsd"]) \
        if "Wsg" in p else jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _swiglu(x, p["Wg"][e], p["Wu"][e],
                                           p["Wd"][e])
    return out, jnp.sort(ids, axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(p, x, config_items, control):
    c = dict(config_items)
    c["rope_scaling"] = dict(c["rope_scaling"]) \
        if c.get("rope_scaling") else None
    with jax.default_matmul_precision("highest"):
        p = _up(p, control)
        eps = c["rms_norm_eps"]
        h = x + _mla(p["attn"], _rms(x, p["norm1_gain"], eps), c)
        z = _rms(h, p["norm2_gain"], eps)
        if "moe" in p:
            f, ids = _experts(p["moe"], z, c)
        else:
            f, ids = _swiglu(z, p["Wg"], p["Wu"], p["Wd"]), None
        return h + f, ids


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(norm, head, x, eps, control):
    with jax.default_matmul_precision("highest"):
        norm, head = _up(norm, control), _up(head, control)
        return _rms(x, norm["gain"], eps) @ head["W"]


def _static(config):
    """What the equations read of the configuration, hashable."""
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rms_norm_eps", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob",
            "held_first_expert")
    items = [(k, config[k]) for k in keys if k in config]
    sc = config.get("rope_scaling")
    items.append(("rope_scaling",
                  tuple(sorted(sc.items())) if sc else None))
    return tuple(items)


def _forward(params, ids, config, control):
    ids = jnp.asarray(ids, jnp.int32)
    x = _up(params[0]["W"][ids], control)
    static, chosen = _static(config), []
    for p in params[1:-2]:
        x, sel = _block(p, x, static, bool(control))
        if sel is not None:
            chosen.append(sel)
    z = _head(params[-2], params[-1], x, config["rms_norm_eps"],
              bool(control))
    return z, chosen


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True``: every weight rounded to float8_e4m3 first."""
    return _forward(params, ids, config, control)[0]


def selected_experts(params, ids, config):
    """Per expert layer, the (T, k) experts the float32 router
    selects, in ascending order (what measure scripts hold the
    program's own selection against)."""
    return _forward(params, ids, config, False)[1]
