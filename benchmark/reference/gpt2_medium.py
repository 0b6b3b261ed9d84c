"""Plain reference for the ``gpt2_medium`` configuration.

GPT-2 (Radford et al. 2019; openai-community/gpt2-medium config.json)
as a straightforward ``jax.numpy`` forward pass: float32, matmuls under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching beyond a loop over rows. It imports nothing of the program and
is given weights the BENCHMARK made from the seed (harness/weights.py),
in the tree shape the configuration's builder declares:

    [ {"W": (V, D)},                                  token embedding
      {"ln1_g","ln1_b","attn":{"Wq","Wk","Wv","Wo","bo"},
       "ln2_g","ln2_b","W1","b1","W2","b2"} x n_layer, pre-LN blocks
      {"W": (D, V), "b": (V,)} ]                      output head

Departures from the published GPT-2, each because the program has no
such layer (they are also listed in configs/gpt2_medium.json):

  D1  no learned position embedding (wpe): h0 = wte[ids] only.
  D2  the output head is its own (D, V) matrix with a bias, not tied
      to wte.
  D3  no bias on the q, k, v projections (c_attn has one in GPT-2);
      the output projection keeps its bias.
  D4  no final layer norm (ln_f) before the head.
  D5  GELU is the tanh approximation (gelu_new) -- as published.
  D6  layer-norm epsilon 1e-5 -- as published.

Everything else is as published: pre-LN residual blocks, 16 heads of
64, scores scaled by 1/sqrt(64), causal mask, 4x GELU MLP, softmax
cross-entropy on next-token targets averaged over batch and time.

The CONTROL of benchmark/tests/ puts this reference, computed in the
precision below the one the configuration states (float32 tensors),
in the program's place; ``correct`` must reject it. It is bfloat16
throughout (``control_cast``): in training weights, activations,
gradients and optimizer state, in serving weights and activations
(``logits(..., control=True)``).
"""

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(p, x, n_head):
    """One pre-LN block on one row: x is (T, D)."""
    T, D = x.shape
    mm = jnp.matmul
    h = _ln(x, p["ln1_g"], p["ln1_b"])
    a = p["attn"]
    split = lambda y: y.reshape(T, n_head, D // n_head).transpose(1, 0, 2)
    q, k, v = (split(mm(h, a["Wq"])), split(mm(h, a["Wk"])),
               split(mm(h, a["Wv"])))
    s = jnp.einsum("htd,hsd->hts", q, k) / jnp.sqrt(
        jnp.asarray(D // n_head, x.dtype))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None], s, jnp.asarray(-1e30, s.dtype))
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    o = jnp.einsum("hts,hsd->htd", w, v).transpose(
        1, 0, 2).reshape(T, D)
    x = x + mm(o, a["Wo"]) + a["bo"]
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    return x + mm(_gelu_new(mm(h, p["W1"]) + p["b1"]), p["W2"]) + p["b2"]


def row_logits(params, ids, n_head, remat=False):
    """Logits (T, V) of one row of token ids (T,)."""
    x = params[0]["W"][ids]
    blk = functools.partial(_block, n_head=n_head)
    if remat:
        blk = jax.checkpoint(blk)
    for p in params[1:-1]:
        x = blk(p, x)
    return x @ params[-1]["W"] + params[-1]["b"]


def row_loss(params, ids, targets, n_head):
    """Sum over time of the next-token cross-entropy of one row."""
    z = row_logits(params, ids, n_head, remat=True).astype(jnp.float32)
    return jnp.sum(jax.nn.logsumexp(z, axis=-1)
                   - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


@functools.partial(jax.jit, static_argnames=("n_head",))
def _row_value_and_grad(params, ids, targets, n_head):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(row_loss)(params, ids, targets, n_head)


def control_cast(tree):
    """The control: weights, activations, gradients and optimizer
    state all in bfloat16."""
    return cast(tree, jnp.bfloat16)


def loss_and_grads(params, batch, config, control=False):
    """(``control`` changes nothing here: the control's bfloat16 comes
    from ``control_cast`` of the weights.) Mean loss over (B, T) and its gradient, one row at a time so the
    reference fits beside nothing else on the chip. ``batch`` is
    (ids (B, T) int32, targets (B, T) int32)."""
    ids, targets = batch
    B, T = ids.shape
    total, grads = 0.0, None
    for r in range(B):
        l, g = _row_value_and_grad(params, jnp.asarray(ids[r]),
                                   jnp.asarray(targets[r]),
                                   config["n_head"])
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    scale = jnp.asarray(1.0 / (B * T), jnp.float32)
    return total.astype(jnp.float32) * scale, jax.tree_util.tree_map(
        lambda g: (g * scale.astype(g.dtype)), grads)


@functools.partial(jax.jit, static_argnames=("n_head", "control"))
def _logits(params, ids, n_head, control):
    if control:
        params = control_cast(params)
    with jax.default_matmul_precision("highest"):
        return row_logits(params, ids, n_head).astype(jnp.float32)


def logits(params, ids, config, control=False):
    """(T, V) float32 logits for one row of ids: the serving check.
    ``control=True`` is the SERVING control: weights and activations
    in bfloat16 throughout. The program's matmuls already round their
    operands to bfloat16 (the TPU's default precision, which the
    configuration states) but keep float32 between them, so the
    control's tokens lie as close to this reference's best as the
    program's own; its distribution lies about 2.6 times as far in
    every log-probability, 7 times in divergence, and that is the
    number ``correct`` holds it to (PERF.md has the readings)."""
    return _logits(params, jnp.asarray(ids, jnp.int32), config["n_head"],
                   bool(control))


def batch_of(features, labels):
    """The traffic's batch (float32 ids, dense one-hot (B, T, V)
    labels) -> (ids, targets) int32, what the published loss takes."""
    import numpy as np
    return (np.asarray(features).astype("int32"),
            np.argmax(labels, axis=-1).astype("int32"))
