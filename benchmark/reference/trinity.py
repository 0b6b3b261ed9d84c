"""Plain reference for the ``trinity_mini_ep16`` configuration.

Arcee Trinity Mini (arcee-ai/Trinity-Mini config.json, ``afmoe``; the
layer's wiring from the public ``modeling_afmoe.py`` of
``transformers``, listed under ``assumed`` in the configuration file)
as a straightforward ``jax.numpy`` pass over ONE row of ids: float32,
matmuls under ``jax.default_matmul_precision("highest")``, no kernels,
the window as a mask over the whole score matrix, every held expert
applied to every token and masked. It imports nothing of the program
and is given the weights the BENCHMARK made from the seed
(harness/weights.py), in the tree the configuration's builder
declares:

    [ {"W": (V, D)},                                  token embedding
      {"norm1_gain","norm1_post_gain","norm2_gain","norm2_post_gain",
       "attn": {"Wq","Wgate": (D, H*128), "Wk","Wv": (D, Hk*128),
                "Wo": (H*128, D), "q_norm_gain","k_norm_gain": (128,)},
       "Wg","Wu": (D, F), "Wd": (F, D)}               a dense layer
      {... "moe": {"Wr": (D, E), "br": (E,),
                   "Wg","Wu": (held, D, W), "Wd": (held, W, D),
                   "Wsg","Wsu": (D, W), "Wsd": (W, D)}},   an expert layer
      {"gain": (D,)},                                 final RMSNorm
      {"W": (D, V)} ]                                 head (untied)

Block ``i`` is the published layer ``first_layer + i``. Equations (eps
``rms_norm_eps``; no bias but the router's; ``rms(.; g)`` is RMSNorm
with its own gain):

  model    x0 = E[ids] * sqrt(hidden_size)  (``mup_enabled``);
           logits = rms(x_last; gain) Wh;
           loss = mean next-token cross-entropy over the slice's ids
  block    h = x + rms(Attn(rms(x; g1)); g1'),
           y = h + rms(F(rms(h; g2)); g2')
  Attn     q = n Wq (32 heads of 128), k = n Wk, v = n Wv (4 heads;
           query head i reads key/value head i // 8), gate = n Wgate;
           every query and key head RMS-normed over its 128 values
           (one gain for all query heads, one for all key heads);
           where ``layer_types`` says ``sliding_attention`` q and k
           are rotated whole (half-split pairs, ``rope_theta``) and
           key j is visible to query i iff i - 2048 < j <= i, where it
           says ``full_attention`` nothing is rotated and every j <= i
           is visible; scores x 128^-0.5, softmax in float32, o = P v,
           out = (o * sigmoid(gate)) Wo
  F dense  (silu(z Wg) * z Wu) Wd, where the published layer is below
           the published ``num_dense_layers``
  experts  s = sigmoid(z Wr) in float32 over ``router_experts``;
           T = the 8 largest of s + br (the bias enters the selection
           only); w_e = s_e / (sum_T s + 1e-20) * ``route_scale``;
           F(z) = SwiGLU_shared(z) + sum_{e in T, e held} w_e
           SwiGLU_e(z): the share's part (held experts
           ``held_first_expert`` ..), what absent experts would add
           is left out

Computed in blocks so that a row of 8,192 fits beside the weights,
their gradient and Adam's moments: a layer at a time and a query head
at a time under ``jax.checkpoint`` (one (T, T) score matrix alive), an
expert at a time.

The CONTROL of benchmark/tests/ puts this reference, computed in the
precision below the one the configuration states (float32 tensors), in
the program's place; ``correct`` must reject it. It is bfloat16
throughout (``control_cast``): weights, activations, gradients and
optimizer state; scores, softmax and router scores stay float32 as the
configuration's ``precision`` states them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, gain, eps):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(F32)).astype(x.dtype)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _rope(x, theta):
    """x (T, N, d): every head rotated whole in half-split pairs;
    position = row index."""
    T, d = x.shape[0], x.shape[-1]
    freqs = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(T, dtype=np.float64)[:, None] * freqs).astype(
        np.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(p, n, c, window):
    """Attn(n) for normed n (T, D); ``window`` None on a full layer."""
    T = n.shape[0]
    H, K, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    eps = c["rms_norm_eps"]
    q = _rms((n @ p["Wq"]).reshape(T, H, d), p["q_norm_gain"], eps)
    k = _rms((n @ p["Wk"]).reshape(T, K, d), p["k_norm_gain"], eps)
    v = (n @ p["Wv"]).reshape(T, K, d)
    if window is not None:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    seen = jnp.asarray(seen)

    @jax.checkpoint
    def head(args):
        q_h, h = args                                  # (T, d), ()
        k_h, v_h = k[:, h // (H // K)], v[:, h // (H // K)]
        s = (q_h @ k_h.T).astype(F32) * d ** -0.5
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return w.astype(v_h.dtype) @ v_h

    o = jax.lax.map(head, (q.transpose(1, 0, 2), jnp.arange(H)))
    o = o.transpose(1, 0, 2).reshape(T, H * d)
    return (o * jax.nn.sigmoid(n @ p["Wgate"])) @ p["Wo"]


def _experts(p, z, c):
    """F(z) of an expert layer for normed z (T, D): the shared expert
    and the held experts' part."""
    s = jax.nn.sigmoid((z @ p["Wr"]).astype(F32))
    _, ids = jax.lax.top_k(s + p["br"].astype(F32),
                           c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * c["route_scale"]

    @jax.checkpoint
    def one(out, expert):
        e, wg, wu, wd = expert
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        return out + (w_e[:, None] * _swiglu(z, wg, wu, wd).astype(F32)
                      ).astype(out.dtype), None

    held = p["Wg"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(z),
        (c["held_first_expert"] + jnp.arange(held), p["Wg"], p["Wu"],
         p["Wd"]))
    return routed + _swiglu(z, p["Wsg"], p["Wsu"], p["Wsd"])


def _block(p, x, c, layer):
    """One block on one row: x (T, D); ``layer`` the published index."""
    eps = c["rms_norm_eps"]
    window = (c["sliding_window"]
              if c["layer_types"][layer] == "sliding_attention" else None)
    if ("moe" in p) == (layer < c["published"]["num_dense_layers"]):
        raise ValueError(f"published layer {layer}: the weights "
                         "disagree with the published num_dense_layers")
    a = _attention(p["attn"], _rms(x, p["norm1_gain"], eps), c, window)
    h = x + _rms(a, p["norm1_post_gain"], eps)
    z = _rms(h, p["norm2_gain"], eps)
    f = (_experts(p["moe"], z, c) if "moe" in p
         else _swiglu(z, p["Wg"], p["Wu"], p["Wd"]))
    return h + _rms(f, p["norm2_post_gain"], eps)


def _frozen(config):
    """The configuration's numbers the equations read, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "sliding_window",
            "num_experts_per_tok", "route_scale", "held_first_expert",
            "first_layer", "hidden_size")
    return (tuple((k, config[k]) for k in keys)
            + (("layer_types", tuple(config["layer_types"])),
               ("published_dense",
                config["published"]["num_dense_layers"])))


def _thaw(frozen):
    c = dict(frozen)
    c["published"] = {"num_dense_layers": c.pop("published_dense")}
    return c


def row_logits(params, ids, c):
    """Logits (T, V) of one row of token ids (T,)."""
    emb = params[0]["W"]
    x = (emb[ids].astype(F32) * c["hidden_size"] ** 0.5).astype(emb.dtype)
    for i, p in enumerate(params[1:-2]):
        x = jax.checkpoint(functools.partial(
            _block, c=c, layer=c["first_layer"] + i))(p, x)
    return _rms(x, params[-2]["gain"], c["rms_norm_eps"]) @ params[-1]["W"]


def row_loss(params, ids, targets, c):
    """Mean over time of the next-token cross-entropy of one row."""
    z = row_logits(params, ids, c).astype(F32)
    return jnp.mean(jax.nn.logsumexp(z, axis=-1)
                    - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])


@functools.partial(jax.jit, static_argnames=("frozen",))
def _row_value_and_grad(params, ids, targets, frozen):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(row_loss)(params, ids, targets,
                                            _thaw(frozen))


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def control_cast(tree):
    """The control: weights, activations, gradients and optimizer
    state all in bfloat16."""
    return cast(tree, jnp.bfloat16)


def loss_and_grads(params, batch, config, control=False):
    """Mean loss over (B, T) and its gradient, one row at a time.
    ``batch`` is (ids (B, T) int32, targets (B, T) int32). (``control``
    changes nothing here: the control's bfloat16 comes from
    ``control_cast`` of the weights.)"""
    ids, targets = batch
    total, grads = 0.0, None
    for r in range(ids.shape[0]):
        l, g = _row_value_and_grad(params, jnp.asarray(ids[r]),
                                   jnp.asarray(targets[r]),
                                   _frozen(config))
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    scale = 1.0 / ids.shape[0]
    return (total.astype(F32) * scale, jax.tree_util.tree_map(
        lambda g: g * jnp.asarray(scale, g.dtype), grads))


@functools.partial(jax.jit, static_argnames=("frozen", "control"))
def _logits(params, ids, frozen, control):
    if control:
        params = control_cast(params)
    with jax.default_matmul_precision("highest"):
        return row_logits(params, ids, _thaw(frozen)).astype(F32)


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids; ``control=True``:
    weights and activations in bfloat16 throughout."""
    return _logits(params, jnp.asarray(ids, jnp.int32), _frozen(config),
                   bool(control))


def batch_of(features, labels):
    """The traffic's batch (float32 ids, dense one-hot (B, T, V)
    labels) -> (ids, targets) int32, what the published loss takes."""
    return (np.asarray(features).astype("int32"),
            np.argmax(labels, axis=-1).astype("int32"))
