"""Jitted public wrappers around the Pallas kernels.

``on_tpu()`` flips interpret mode automatically: interpret=True on CPU
(validation), compiled Mosaic on real TPUs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .coded_reduce import coded_combine_call
from .encode import srht_encode_call
from .fused_step import fused_enabled, fused_masked_gradient
from .fwht import fwht_kernel_call

__all__ = ["on_tpu", "fwht", "hadamard_encode", "srht_encode",
           "coded_combine", "fused_masked_gradient", "fused_enabled"]


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def fwht(x: jax.Array, axis: int = -1) -> jax.Array:
    """Fast Walsh-Hadamard transform along ``axis`` (power-of-two length)."""
    interpret = not on_tpu()
    x = jnp.moveaxis(x, axis, -1)
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    out = fwht_kernel_call(flat, interpret=interpret)
    return jnp.moveaxis(out.reshape(lead + (x.shape[-1],)), -1, axis)


def srht_encode(X: jax.Array, cols: np.ndarray, signs: np.ndarray, N: int,
                lo: int = 0, hi: int | None = None) -> jax.Array:
    """Rows [lo, hi) of  S X = H_N[:, cols] diag(signs) X / sqrt(n)  for data
    X (n, p) — the matrix-free SRHT encode (paper §4.2.2).

    One XLA scatter places the data columns into their N transform positions;
    the fused Pallas kernel (kernels/encode.py) then does sign-flip + all
    FWHT butterfly stages + the contiguous row gather in a single pass.
    Returns (hi - lo, p); S is never formed.
    """
    n, p = X.shape
    hi = N if hi is None else hi
    xt = jnp.zeros((p, N), X.dtype).at[:, jnp.asarray(cols)].set(X.T)
    dsigns = jnp.zeros((1, N), X.dtype).at[0, jnp.asarray(cols)].set(
        jnp.asarray(signs, X.dtype))
    out = srht_encode_call(xt, dsigns, lo=lo, hi=hi,
                           scale=1.0 / math.sqrt(n), interpret=not on_tpu())
    return out.T


def hadamard_encode(X: jax.Array, cols: np.ndarray, signs: np.ndarray,
                    N: int | None = None) -> jax.Array:
    """Encode data X (n, p) with the randomized Hadamard ensemble:

        S X = H_N[:, cols] diag(signs) X / sqrt(n)

    via the fused sign-flip + FWHT + gather kernel (paper §4.2.2) —
    no S materialization.  Returns (N, p).
    """
    n, p = X.shape
    N = N or 1 << (2 * n - 1).bit_length()  # default beta ~= 2 padding
    return srht_encode(X, cols, signs, N)


def coded_combine(g: jax.Array, c: jax.Array) -> jax.Array:
    """Fused coded gradient combine: sum_i c_i g_i for (m, P) grads.

    The kernel itself pads P to a block multiple and resolves interpret
    mode from the backend, so this is a plain alias kept for API stability.
    """
    return coded_combine_call(g, c)
