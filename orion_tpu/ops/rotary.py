"""Rotary position embeddings (RoPE) for the softmax/sliding-window layers.

Linear-attention layers use learned absolute positions (rotating phi-space
vectors breaks the kernel trick); the softmax and sliding-window layers of
the hybrid model family use RoPE. Supports an offset for decode-time single
positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def rotary_freqs(head_dim: int, max_t: int, base: float = 10000.0) -> Array:
    """[max_t, head_dim//2] angle table."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_t, dtype=jnp.float32)
    return jnp.outer(t, inv)  # [T, D/2]


def _rotate(x: Array, ang: Array) -> Array:
    """Shared pair-rotation body. ang broadcasts against x's leading dims."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_rotary(x: Array, angles: Array) -> Array:
    """Rotate pairs. x: [..., T, D]; angles: [T, D/2] (or broadcastable)."""
    return _rotate(x, angles)


def apply_rotary_at(x: Array, angles_table: Array, positions: Array) -> Array:
    """Decode-time: x [..., D] at integer positions [...]. Gathers angles."""
    return _rotate(x, angles_table[positions])


def apply_rotary_half(x: Array, rotary_dims: int, base: float) -> Array:
    """The rotate-half convention on the first ``rotary_dims`` of x ``[...,
    T, Dh]`` at positions 0..T-1: dim ``j`` is paired with ``j +
    rotary_dims / 2`` (not with its neighbour) and rotated by ``t *
    base^(-2j / rotary_dims)``; the rest of the head is untouched."""
    half = rotary_dims // 2
    ang = rotary_freqs(rotary_dims, x.shape[-2], base)  # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dims], xf[..., rotary_dims:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)
    return out.astype(x.dtype)


__all__ = ["rotary_freqs", "apply_rotary", "apply_rotary_at", "apply_rotary_half"]
