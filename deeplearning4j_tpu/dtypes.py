"""Dtype policy for TPU execution.

The reference framework (ND4J) has a single global dtype
(float/double/half) set process-wide. On TPU the idiomatic split is:
parameters and optimizer state in float32, matmul/conv compute in
bfloat16 (MXU-native), reductions and losses in float32.

A :class:`Policy` captures that split; layers consult the active policy
when casting inputs to compute dtype and always keep parameters in
``param_dtype``. Gradient-check tests switch the policy to float64-free
"highest" (f32 everywhere — TPU has no f64 MXU path; checks run on CPU
with jax_enable_x64 where needed).
"""

from __future__ import annotations

import dataclasses
from contextlib import contextmanager

import jax.numpy as jnp

__all__ = ["Policy", "policy", "set_policy", "default_policy",
           "highest_precision", "promote_half", "einsum_f32"]


def promote_half(x):
    """float32 if ``x`` is half precision (bf16/f16), otherwise
    UNCHANGED — loss heads use this so bf16 hidden activations get
    promoted before exp/log math without downcasting the f64 arrays
    the gradient checker runs under ``jax_enable_x64``."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x


def einsum_f32(subscripts, a, b):
    """``einsum`` of two operands of one dtype, accumulated AND
    returned in float32: where a half-precision product feeds a
    softmax, a router or a loss, it is not rounded to half on the
    way."""
    return jnp.einsum(subscripts, a, b.astype(a.dtype),
                      preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32

    def cast_to_compute(self, x):
        return jnp.asarray(x, self.compute_dtype)

    def cast_to_output(self, x):
        return jnp.asarray(x, self.output_dtype)


# f32 default: numerically safe everywhere; switch to bf16 compute for
# benchmark speed with ``set_policy(tpu_bf16())``.
_DEFAULT = Policy()
_active = _DEFAULT


def default_policy() -> Policy:
    return _DEFAULT


def tpu_bf16() -> Policy:
    """bf16 compute AND bf16 hidden activations / f32 params — the
    MXU-native training policy. Keeping inter-layer activations in
    bfloat16 halves the HBM traffic of every elementwise/BN boundary
    (the policy the ``resnet50_train`` cell runs under: PERF.md
    section 4);
    output layers promote logits to f32 before softmax/loss
    (output.py), and BN statistics accumulate in f32 regardless
    (normalization.py)."""
    return Policy(compute_dtype=jnp.bfloat16,
                  output_dtype=jnp.bfloat16)


def highest_precision() -> Policy:
    return Policy()


def policy() -> Policy:
    return _active


def set_policy(p: Policy) -> None:
    global _active
    _active = p


@contextmanager
def policy_scope(p: Policy):
    global _active
    prev = _active
    _active = p
    try:
        yield p
    finally:
        _active = prev
