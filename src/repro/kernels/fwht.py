"""Pallas TPU kernel: blocked Fast Walsh-Hadamard Transform.

The paper's efficient encoder (§4.2.2) is FWHT over the (zero-padded, sign-
flipped) data — the dominant encode cost.  GPU implementations make log2(N)
passes over global memory; the TPU-native layout instead keeps a (BLOCK_ROWS,
N) tile resident in VMEM across ALL butterfly stages (one HBM round-trip
total).  The transform axis is the trailing (lane) axis.

The lane axis is never split below the 128-lane vreg width (Mosaic refuses
such shape casts).  With b = min(N, 128) and N = q * b, the Sylvester
Hadamard factors as H_N = H_q (x) H_b, so the transform is

  1. one (rows, b) @ (b, b) f32 matmul by H_b per b-lane chunk (the seven
     lowest butterfly stages, on the MXU at ``Precision.HIGHEST``);
  2. a q-point butterfly over whole chunks: aligned vreg add/sub on the VPU.

Grid: one program per row block.  BLOCK_ROWS is chosen so the input and
output tiles, double-buffered, plus the transform's temporaries fit an 8 MB
VMEM budget (half of the 16 MB scoped VMEM of a v5e core, leaving headroom
for the compiler's own buffers); the calls zero-pad the rows to a whole
number of blocks, so every block meets the (8, 128) tiling rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["fwht_kernel_call", "pick_block_rows", "butterfly",
           "hadamard_tile", "row_blocks", "default_interpret"]

LANES = 128


def pick_block_rows(rows: int, n: int, dtype_bytes: int = 4,
                    vmem_budget: int = 8 * 1024 * 1024) -> int:
    """Largest power-of-two row block whose tile (+ double buffer) fits the
    VMEM budget and divides ``rows``."""
    br = 1
    while (br * 2 <= rows and (br * 2) * 2 * n * dtype_bytes <= vmem_budget):
        br *= 2
    while rows % br:
        br //= 2
    return max(br, 1)


def row_blocks(rows: int, n: int) -> tuple[int, int]:
    """(block_rows, pad) for a (rows, n) transform: the budget-limited
    power-of-two block capped at the power of two covering ``rows``, and the
    zero rows that make ``rows + pad`` a whole number of blocks.  A block is
    then either a multiple of 8 rows or the whole padded array — the TPU
    tiling rule holds for any ``rows``.  The budget counts four f32 tiles:
    the input and the output are each double-buffered, and the chunk
    temporaries of the transform take about as much again (at n = 8192 a
    128-row block overran the 16 MB scoped VMEM of a v5e)."""
    br = min(pick_block_rows(1 << 30, 4 * n),
             1 << max(rows - 1, 1).bit_length())
    return br, (-rows) % br


def hadamard_tile(n: int) -> jax.Array:
    """The (b, b) Sylvester Hadamard factor, b = min(n, 128), that the
    kernels multiply each lane chunk by; passed to them as an operand."""
    b = min(n, LANES)
    idx = np.arange(b)
    parity = np.vectorize(lambda v: bin(v).count("1") & 1)(idx[:, None] & idx)
    return jnp.asarray(1.0 - 2.0 * parity, jnp.float32)


def butterfly(x: jax.Array, h: jax.Array) -> list:
    """H_n x over the trailing axis of a (rows, n) float32 tile, returned as
    the n // b output lane chunks of width b = h.shape[0] — shared by every
    kernel body that transforms in VMEM (fwht.py, encode.py)."""
    b = h.shape[0]
    chunks = [jax.lax.dot(x[:, c * b:(c + 1) * b], h,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
              for c in range(x.shape[1] // b)]
    s = 1
    while s < len(chunks):
        for lo in range(0, len(chunks), 2 * s):
            for t in range(lo, lo + s):
                a, c = chunks[t], chunks[t + s]
                chunks[t], chunks[t + s] = a + c, a - c
        s *= 2
    return chunks


def default_interpret() -> bool:
    """Interpret everywhere but real TPUs — the kernels assume the TPU
    lane layout, so GPU backends validate in interpret mode like CPU (the
    same policy as ops.on_tpu)."""
    return jax.default_backend() != "tpu"


def _fwht_body(x_ref, h_ref, o_ref):
    """In-VMEM transform over the trailing axis (length n, power of two)."""
    chunks = butterfly(x_ref[...].astype(jnp.float32), h_ref[...])
    o_ref[...] = jnp.concatenate(chunks, axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def fwht_kernel_call(x: jax.Array, *, block_rows: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """FWHT along the last axis of x: (rows, n) -> (rows, n).

    n must be a power of two.  interpret=None (default) picks the mode from
    the backend: compiled Mosaic on TPU, interpreted elsewhere.  Without an
    explicit ``block_rows`` the rows are zero-padded to whole blocks.
    """
    if interpret is None:
        interpret = default_interpret()
    rows, n = x.shape
    if n & (n - 1):
        raise ValueError(f"FWHT length {n} is not a power of two")
    if block_rows is None:
        br, pad = row_blocks(rows, n)
    elif rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block_rows "
                         f"{block_rows}")
    else:
        br, pad = block_rows, 0
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    h = hadamard_tile(n)
    out = pl.pallas_call(
        _fwht_body,
        grid=((rows + pad) // br,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec(h.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, n), x.dtype),
        interpret=interpret,
    )(xp, h)
    return out[:rows] if pad else out
