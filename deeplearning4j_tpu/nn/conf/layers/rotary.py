"""Rotary position embeddings (Su et al. 2021), shared by the
attention layers that rotate their queries and keys: the inverse
frequencies, with YaRN's blend where a layer asks for it, and the
rotation itself."""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["rope", "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Rotary inverse frequencies of ``dim // 2`` pairs. With a
    ``yarn`` scaling: extrapolated (``theta**(-2i/dim)``) and
    interpolated (the same over ``factor``) frequencies blended per
    dimension by the linear ramp between the dimensions that make
    ``beta_fast`` and ``beta_slow`` rotations over the original
    context (Peng et al. 2023, as the DeepSeek-V2 reference code)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra.astype(np.float32)
    if scaling.get("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling type {scaling.get('type')!r}: "
                         "only 'yarn' is implemented")
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def rope(x, positions, inv_freq, scale=1.0, halves=False):
    """Rotate pairs of the last axis by ``positions * inv_freq[i]``:
    the interleaved pairs ``(x[2i], x[2i+1])``, or with ``halves``
    the pairs ``(x[i], x[i + d/2])``. ``positions`` broadcasts
    against ``x``'s leading axes. The result is laid out
    ``[first of each pair, second of each pair]`` (with ``halves``
    that is the input's own order); queries and keys go through here
    alike, so their products do not see the order."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    if halves:
        a, b = jnp.split(xf, 2, axis=-1)
    else:
        a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)
