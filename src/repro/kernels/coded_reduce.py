"""Pallas TPU kernel: fused coded gradient combine.

The per-step aggregation  g~ = sum_i c_i * g_i  over the worker-stacked
gradient block (m, P) with FRC decode weights c (m,) — the master-side
hot path of every iteration (paper Algorithm 1 line 7).  Fusing the mask,
scale and reduction avoids materializing the (m, P) weighted intermediate
in HBM: the tile is weighted and reduced in VMEM in one pass.

Grid over P blocks; the worker axis (m <= 32) rides along the sublane dim.

Layout is resolved ONCE per gradient width (``combine_layout``, lru-cached):
instead of zero-padding P up to a block multiple on every call, the block
width snaps to the largest multiple-of-128 divisor of P under the cap, so
for the widths encoders and models produce the kernel tiles the array
exactly and the per-step pad disappears from the traced program.  Padding
only survives as a last resort when P has no such divisor — e.g. the ridge
paper width 6000, or a large prime P.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fwht import LANES, default_interpret

__all__ = ["coded_combine_call", "combine_layout"]


@functools.lru_cache(maxsize=None)
def combine_layout(P: int, block: int = 2048) -> tuple[int, int]:
    """(block_width, pad) for a width-P combine.  The block is the whole P
    when P <= block, else a multiple of 128 (the TPU lane tiling rule).
    pad == 0 whenever P has such a divisor under ``block`` (true for the
    power-of-two-ish widths encoders produce) — the pad then never enters
    the traced program.  Otherwise P is padded to whole blocks, with the
    block sized to waste fewer than 128 columns per block."""
    if P <= block:
        return P, 0
    top = block // LANES * LANES
    for bp in range(top, 0, -LANES):
        if P % bp == 0:
            return bp, 0
    nblocks = -(-P // top)
    bp = -(-P // (nblocks * LANES)) * LANES
    return bp, nblocks * bp - P


def _combine_body(g_ref, c_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)        # (m, BP)
    c = c_ref[...].astype(jnp.float32)        # (m, 1)
    o_ref[...] = jnp.sum(g * c, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def coded_combine_call(g: jax.Array, c: jax.Array, *, block: int = 2048,
                       interpret: bool | None = None) -> jax.Array:
    """g: (m, P) worker gradients; c: (m,) or (m, 1) decode weights -> (P,).

    interpret=None (default) picks the mode from the backend: compiled
    Mosaic on TPU, interpreted elsewhere (the ``fwht.py`` policy).  Callers
    on the hot path (``core.data_parallel._masked_mean``) hand c already
    shaped (m, 1) so no per-step reshape is traced; the 1-D form is kept
    for API compatibility.
    """
    if interpret is None:
        interpret = default_interpret()
    m, P = g.shape
    if c.ndim == 1:
        c = c[:, None]
    bp, pad = combine_layout(P, block)
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
    padded = P + pad
    out = pl.pallas_call(
        _combine_body,
        grid=(padded // bp,),
        in_specs=[pl.BlockSpec((m, bp), lambda i: (0, i)),
                  pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), g.dtype),
        interpret=interpret,
    )(g, c)
    return out[0, :P] if pad else out[0]
