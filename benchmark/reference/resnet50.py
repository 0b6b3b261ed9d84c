"""Plain reference for the ``resnet50`` configuration.

ResNet-50 of He et al. 2015 (arXiv:1512.03385, table 1, 50-layer) as a
straightforward ``jax.numpy`` forward pass in float32 with convolutions
under ``jax.default_matmul_precision("highest")``: 7x7/2 stem, 3x3/2
max-pool, stages of 3, 4, 6, 3 bottleneck blocks (1x1 -> 3x3 -> 1x1,
widths 64/256, 128/512, 256/1024, 512/2048), projection shortcuts
where the shape changes (option B), batch normalization after every
convolution and before the ReLU, global average pool, 1000-way
fully-connected layer, softmax cross-entropy averaged over the batch.
It imports nothing of the program and is given weights the BENCHMARK
made from the seed, in the tree the builder declares: a dict by layer
name, ``<block>_<a|b|c|sc>_conv: {"W": (kh, kw, cin, cout)}``,
``..._bn: {"gamma", "beta"}``, ``stem_conv``/``stem_bn``,
``out: {"W": (2048, classes), "b"}``.

Batch normalization in training mode: statistics of the batch over
(N, H, W), biased variance, epsilon 1e-5 (Ioffe & Szegedy 2015).

Departures from the paper, each because the program's zoo model does
so (listed in configs/resnet50.json too):

  D1  "same" padding: the 7x7/2 stem pads (2, 3), not (3, 3); the
      3x3/2 max-pool pads (0, 1). Output sizes are the paper's.
  D2  the stride of a stage's first block sits on its first 1x1
      convolution -- as published (later "v1.5" variants move it).
  D3  convolutions have no bias (batch norm follows each).
  D4  inputs are N(0, 1) noise, not ImageNet crops: speed and
      agreement need no data set.

The CONTROL (``control=True``) casts the operands of every convolution
and of the fully-connected layer to float8_e4m3, forward and backward
(no loss scaling): the precision below the bfloat16 compute the
configuration states. Rounding only the forward operands (straight-
through backward) reads the same gaps as the program's own bfloat16
(PERF.md, PR 23), so it is no control.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
STAGES = ((3, 1), (4, 2), (6, 2), (3, 2))      # (blocks, first stride)


def _q8(x):
    """Cast to float8_e4m3 and back; the cotangent takes the same
    cast on its way back, as in a float8 matmul without loss scaling."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _conv(x, w, stride, control):
    if control:
        x, w = _q8(x), _q8(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, relu=True):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) / jnp.sqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    return jnp.maximum(y, 0.0) if relu else y


def _conv_bn(params, name, x, stride, control, relu=True):
    return _bn(_conv(x, params[name + "_conv"]["W"], stride, control),
               params[name + "_bn"], relu)


def _block(bp, x, pre, stride, project, control):
    a = _conv_bn(bp, pre + "_a", x, stride, control)
    b = _conv_bn(bp, pre + "_b", a, 1, control)
    c = _conv_bn(bp, pre + "_c", b, 1, control, relu=False)
    sc = _conv_bn(bp, pre + "_sc", x, stride, control,
                  relu=False) if project else x
    return jnp.maximum(c + sc, 0.0)


def logits(params, x, control=False):
    h = _conv_bn(params, "stem", x, 2, control)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    for si, (blocks, first) in enumerate(STAGES):
        for bi in range(blocks):
            pre = f"s{si}b{bi}"
            bp = {k: v for k, v in params.items()
                  if k.startswith(pre + "_")}
            blk = jax.checkpoint(functools.partial(
                _block, pre=pre, stride=first if bi == 0 else 1,
                project=bi == 0, control=control))
            h = blk(bp, h)
    h = jnp.mean(h, axis=(1, 2))
    w = params["out"]["W"]
    if control:
        h, w = _q8(h), _q8(w)
    return h @ w + params["out"]["b"]


def loss(params, x, targets, control=False):
    z = logits(params, x, control)
    return jnp.mean(jax.nn.logsumexp(z, axis=-1)
                    - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])


@functools.partial(jax.jit, static_argnames=("control",))
def _value_and_grad(params, x, targets, control):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, x, targets, control)


def loss_and_grads(params, batch, config, control=False):
    """Loss and gradient of the whole batch at once: batch
    normalization couples its rows, so it is not split; blocks are
    rematerialized instead."""
    x, targets = batch
    return _value_and_grad(params, jnp.asarray(x), jnp.asarray(targets),
                           control)


def control_cast(tree):
    """The control keeps float32 storage; its rounding is in the
    forward operands."""
    return tree


def batch_of(features, labels):
    import numpy as np
    return (np.asarray(features, "float32"),
            np.argmax(labels, axis=-1).astype("int32"))
