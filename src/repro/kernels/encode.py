"""Pallas TPU kernel: fused SRHT-style encode (sign-flip + FWHT + row gather).

The paper's efficient encoder (§4.2.2) is  S X = H_N[:, cols] diag(signs) X
/ sqrt(n)  — never materialized.  Written along the transform axis this is

    (S X)[lo:hi] = (FWHT(D_pad · X_pad))[lo:hi] / sqrt(n)

where ``X_pad`` is the data scattered into its N padded positions and
``D_pad`` is the sign vector scattered likewise (zero on dead rows).  The
kernel fuses the three post-scatter stages into ONE pallas_call, one HBM
round-trip per tile:

  1. sign-flip: multiply the (BLOCK_ROWS, N) tile by the broadcast sign row
     (zeros kill any stray values in dead lanes — the zero-pad is enforced
     here, not trusted from the caller);
  2. the FWHT in VMEM (same chunked layout as fwht.py);
  3. row gather: only the output lane chunks covering the encoded-row window
     [lo, hi) — a worker block, or the full frame — are scaled and written
     back to HBM.  The kernel writes the chunk-aligned window; the exact
     [lo, hi) slice happens outside it, so no lane slice is unaligned.

The transform axis is the trailing (lane) axis: callers pass X^T so encoded
ROWS become output lanes and the gather is a static lane slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fwht import butterfly, default_interpret, hadamard_tile, row_blocks

__all__ = ["srht_encode_call"]


def _srht_body(x_ref, d_ref, h_ref, o_ref, *, c0: int, c1: int,
               scale: float):
    chunks = butterfly(x_ref[...].astype(jnp.float32) *
                       d_ref[...].astype(jnp.float32), h_ref[...])
    o_ref[...] = (jnp.concatenate(chunks[c0:c1], axis=1) *
                  scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "scale",
                                             "interpret"))
def srht_encode_call(xt: jax.Array, dsigns: jax.Array, *, lo: int, hi: int,
                     scale: float, interpret: bool | None = None) -> jax.Array:
    """Fused sign-flip + FWHT + row-window encode.

    xt:     (p, N) — data columns as rows, already scattered into the N
            padded transform positions (zeros elsewhere).
    dsigns: (1, N) — random signs at live positions, ZERO at dead ones.
    Returns (p, hi - lo): encoded rows [lo, hi) of S X, transposed.
    The p rows are zero-padded to whole blocks.
    """
    if interpret is None:
        interpret = default_interpret()
    rows, n = xt.shape
    if n & (n - 1):
        raise ValueError(f"transform length {n} is not a power of two")
    if not (0 <= lo < hi <= n):
        raise ValueError(f"row window [{lo}, {hi}) outside [0, {n})")
    if dsigns.shape != (1, n):
        raise ValueError(f"dsigns shape {dsigns.shape} != (1, {n})")
    br, pad = row_blocks(rows, n)
    xp = jnp.pad(xt, ((0, pad), (0, 0))) if pad else xt
    h = hadamard_tile(n)
    b = h.shape[0]
    c0, c1 = lo // b, -(-hi // b)                 # chunks covering [lo, hi)
    out = pl.pallas_call(
        functools.partial(_srht_body, c0=c0, c1=c1, scale=scale),
        grid=((rows + pad) // br,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec(h.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, (c1 - c0) * b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, (c1 - c0) * b),
                                       xt.dtype),
        interpret=interpret,
    )(xp, dsigns, h)
    return out[:rows, lo - c0 * b:hi - c0 * b]
