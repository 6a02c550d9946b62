"""Pallas TPU megakernel: fused masked-gradient step (paper Algorithm 1).

One pallas_call per iteration computes the WHOLE master-side hot path —

    g~ = sum_i c_i * (S_i X)^T (S_i X w - S_i y),
    c_i = mask_i * (m / k) / (n * beta),  k = |active set|

— per worker block: the residual matvec, the erasure mask (a zero decode
weight) and the decode-weighted combine all happen on the same VMEM tile.
The unfused path materializes the (m, p) per-worker gradient stack in HBM
and re-reads it for the combine; here each (br, p) slab of S_i X is
streamed through VMEM exactly once (grid over worker x row blocks, with
Pallas's automatic pipelining double-buffering the slab loads) and only the
(1, p) accumulator ever lives across grid steps.  HBM traffic drops from
~2 m r p + 2 m p to ~m r p elements per step.

Dispatch policy (``fused_enabled``): default on real TPUs only — the
interpreted kernel is slower than XLA's fused einsums on CPU/GPU, so those
backends keep the dense path in ``core.data_parallel._masked_mean``.  The
``REPRO_FUSED`` env var forces it either way (=1 exercises the kernel in
interpret mode for CI trace-equality guards; =0 pins the dense path on TPU).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fwht import LANES, default_interpret

__all__ = ["fused_masked_gradient", "fused_enabled", "pick_fused_block_rows"]


def fused_enabled() -> bool:
    """Should the runners take the fused megakernel path?  Checked at trace
    time (it is a Python-level branch, not a jaxpr one), so flipping
    ``REPRO_FUSED`` between calls of one jitted runner with identical shapes
    will NOT retrace — tests use fresh shapes or subprocesses."""
    env = os.environ.get("REPRO_FUSED")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no")
    return jax.default_backend() == "tpu"


def pick_fused_block_rows(r: int, p: int, dtype_bytes: int = 4,
                          vmem_budget: int = 8 * 1024 * 1024) -> int:
    """Row block for the (br, p) slabs of the per-worker row count ``r``:
    the largest multiple of 128 dividing ``r`` whose slab plus its pipeline
    double-buffer fits the VMEM budget, else the whole ``r``.  A divisor
    means no row padding: the grid tiles ``r`` exactly, and either choice
    meets the TPU's (8, 128) block tiling rule."""
    cap = max(1, vmem_budget // max(1, 2 * p * dtype_bytes))
    for br in range(min(r, cap) // LANES * LANES, 0, -LANES):
        if r % br == 0:
            return br
    return r


def _fused_body(sx_ref, sy_ref, w_ref, c_ref, o_ref):
    """Grid (m, r // br): worker i, row block j.

    Residual matvec + rank-br gradient contribution + weighted accumulate,
    all on the current slab, as exact f32 multiply-reduces on the VPU.  The
    (1, p) output block has a
    constant index map, so it stays pinned in VMEM across every grid step;
    TPU grid iteration is sequential, so the (0, 0) zero-init runs first.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sx = sx_ref[...].astype(jnp.float32)                    # (br, p)
    w = w_ref[...].astype(jnp.float32)                      # (1, p)
    u = (jnp.sum(sx * w, axis=1, keepdims=True)
         - sy_ref[...].astype(jnp.float32))                 # (br, 1) residual
    g = jnp.sum(u * sx, axis=0, keepdims=True)              # (1, p)
    o_ref[...] += c_ref[...] * g                            # c: (1, 1)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def _fused_call(SX: jax.Array, Sy: jax.Array, w: jax.Array, c: jax.Array, *,
                interpret: bool, block_rows: int | None = None) -> jax.Array:
    m, r, p = SX.shape
    br = block_rows or pick_fused_block_rows(r, p, SX.dtype.itemsize)
    # every block's trailing two dims are either whole array dims or
    # (8, 128)-aligned: Sy rides as a (br, 1) column, c as a (1, 1) tile
    out = pl.pallas_call(
        _fused_body,
        grid=(m, r // br),
        in_specs=[pl.BlockSpec((None, br, p), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((None, br, 1), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, p), lambda i, j: (0, 0)),
                  pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, p), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(SX, Sy[:, :, None], w[None, :], c.reshape(m, 1, 1))
    return out[0].astype(w.dtype)


def fused_masked_gradient(SX: jax.Array, Sy: jax.Array, w: jax.Array,
                          mask: jax.Array, *, n: int, beta: float,
                          interpret: bool | None = None,
                          block_rows: int | None = None) -> jax.Array:
    """The fused (1/eta)-scaled masked gradient, (p,).

    SX (m, r, p) / Sy (m, r) are the worker-stacked encoded blocks, w the
    iterate, mask the (m,) {0,1} active set.  Equals
    ``masked_gradient(prob, w, mask)`` from ``core.data_parallel`` to float
    rounding (the trace-equality tests enforce <= 1e-4).  Raw-array API on
    purpose: kernels/ never imports problem containers.

    interpret=None resolves from the backend (compiled Mosaic on TPU,
    interpreted elsewhere — the ``coded_reduce.py`` policy).  Composes with
    ``vmap`` (the batched-trial runners): the batched axis becomes a leading
    grid axis, and the shared SX/Sy operands are NOT broadcast.
    """
    if interpret is None:
        interpret = default_interpret()
    m = SX.shape[0]
    k = jnp.maximum(mask.sum(), 1.0)
    c = (mask * (m / k) / (n * beta)).astype(jnp.float32)[:, None]  # (m, 1)
    return _fused_call(SX, Sy, w, c, interpret=interpret,
                       block_rows=block_rows)
