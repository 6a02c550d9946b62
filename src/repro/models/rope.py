"""Rotary position embeddings, including Qwen2-VL M-RoPE (arXiv:2409.12191)
and YaRN scaling (arXiv:2309.00071) as DeepSeek-V2 configures it.

M-RoPE splits the head_dim/2 rotary frequencies into (temporal, height,
width) sections, each rotated by its own position stream.  Text tokens carry
identical (t, h, w) positions, reducing to standard 1-D RoPE.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rope_angles", "apply_rope", "mrope_angles", "sinusoidal_positions",
           "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """(dim // 2,) inverse frequencies: unscaled below the dim that turns
    beta_fast times over the original context, divided by the factor above
    the one that turns beta_slow times, linear in between."""
    def turns_dim(turns):
        return (dim * math.log(yarn.original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def rope_angles(positions: jax.Array, head_dim: int,
                theta: float = 10000.0,
                yarn=None) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for integer positions (YaRN-scaled where ``yarn``).

    positions: (...,) int32 -> cos, sin each (..., head_dim // 2) float32.
    """
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        mag = 1.0
    else:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn), jnp.float32)
        mag = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if mag == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def mrope_angles(positions: jax.Array, head_dim: int,
                 sections: tuple[int, int, int],
                 theta: float = 10000.0) -> tuple[jax.Array, jax.Array]:
    """M-RoPE cos/sin. positions: (3, B, S) int32 for (t, h, w) streams.

    sections are sizes over the head_dim/2 frequency axis, sum == head_dim/2.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang_all = positions.astype(jnp.float32)[..., None] * freqs  # (3, B, S, half)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[i, ..., start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)                       # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) -> rotated x (same dtype)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dt)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """Additive sinusoidal embeddings (whisper-style stub frontend)."""
    half = d_model // 2
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
