"""Shared layer primitives: RMSNorm, rotary embeddings, initializers."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rms_norm", "rope", "apply_rope", "dense_init", "DTYPES"]

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(positions: jax.Array, dim: int, theta: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embedding. positions [S] -> [S, dim/2]."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, D] rotated pairwise (split-halves convention)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    shape = (1,) * (x.ndim - 2) + cos.shape  # broadcast over leading axes
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def dense_init(key, shape, dtype, scale: float = 1.0):
    """Scaled normal draw, made in float32 and cast to ``dtype`` inside one
    compiled program: only the ``dtype`` result is allocated, never a
    float32 temporary of the weight's size (a 24-layer stacked FFN weight
    at d_model 3840 would otherwise hold a 3.8 GB float32 draw beside a
    bfloat16 model)."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    return (scale * jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)
