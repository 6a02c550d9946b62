"""Pallas TPU megakernel: fused masked-gradient step (paper Algorithm 1).

One pallas_call per iteration computes the WHOLE master-side hot path —

    g~ = sum_i c_i * (S_i X)^T (S_i X w - S_i y),
    c_i = mask_i * (m / k) / (n * beta),  k = |active set|

— per worker block: the residual matvec, the erasure mask (a zero decode
weight) and the decode-weighted combine all happen on the same VMEM tile.
The unfused path materializes the (m, p) per-worker gradient stack in HBM
and re-reads it for the combine; here each (br, p) slab of S_i X is
streamed through VMEM once per call (grid over worker x row blocks, with
Pallas's automatic pipelining double-buffering the slab loads) and only
the accumulator lives across grid steps.

Two bodies, chosen by ``fused_body`` from what the call can see:

* ``"fused"`` — one iterate w: exact f32 multiply-reduces on the VPU into
  a (1, p) accumulator.  HBM traffic is ~m r p elements per call, against
  ~2 m r p + 2 m p for the unfused path.  This is the unbatched call (the
  single-trial runners) and, under ``vmap``, the fallback: Pallas turns the
  vmapped axis into a leading grid axis, so each of the R realizations
  streams all of SX again (R m r p elements a call).
* ``"fused-shared"`` — R > 1 iterates W (R, p) over one shared SX/Sy, the
  batched-trial runners' case.  ``fused_masked_gradient``'s batching rule
  hands the whole (R, p) batch to one call, and each slab is read once for
  all R realizations: U = S_ij X W^T - S_ij y (br, R residuals), then
  G += (U * C_i)^T S_ij X, two skinny f32-faithful (``HIGHEST``) matmuls on
  the MXU into an (R, p) accumulator.  HBM traffic is ~m r p elements a
  call whatever R is.  Taken whenever W is batched while SX and Sy are not
  and W and the accumulator fit VMEM beside a slab (realization blocks
  split a large R).

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fwht import LANES, default_interpret

__all__ = ["fused_masked_gradient", "fused_enabled", "fused_body",
           "kernel_path", "pick_fused_block_rows"]

SUBLANES = 8
# scoped VMEM the shared body may use: its double-buffered slab, W and the
# accumulator, and the MXU's f32 operand splits (a v5e core has 128 MiB)
_SHARED_VMEM_LIMIT = 48 * 1024 * 1024
_SHARED_VMEM_BUDGET = 32 * 1024 * 1024


def fused_enabled() -> bool:
    """Should the runners take the fused megakernel path?  Checked at trace
    time (it is a Python-level branch, not a jaxpr one), so flipping
    ``REPRO_FUSED`` between calls of one jitted runner with identical shapes
    will NOT retrace — tests use fresh shapes or subprocesses."""
    env = os.environ.get("REPRO_FUSED")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no")
    return jax.default_backend() == "tpu"


def _realization_block(R: int, p: int) -> int | None:
    """Realizations per shared-body call block: the largest divisor of R
    (R itself or a multiple of 8, the sublane tiling rule) whose W and
    accumulator, each double-buffered, take at most a quarter of the
    budget; None when no block fits."""
    cap = _SHARED_VMEM_BUDGET // 4 // (4 * 4 * p)
    for bR in range(min(R, cap), 0, -1):
        if R % bR == 0 and (bR == R or bR % SUBLANES == 0):
            return bR
    return None


def fused_body(realizations: int, p: int, shared: bool = True) -> str:
    """The body a fused masked gradient over ``realizations`` iterates of
    width ``p`` runs: ``"fused-shared"`` when R > 1 iterates read one
    shared SX/Sy and a realization block fits VMEM, else ``"fused"`` (the
    per-realization body, one pass over SX per iterate).  The batching rule
    and the runners' span both ask this, so they cannot disagree."""
    if realizations > 1 and shared and _realization_block(realizations, p):
        return "fused-shared"
    return "fused"


def kernel_path(realizations: int, p: int) -> str:
    """The masked-gradient path a runner over ``realizations`` iterates
    takes: ``"dense"`` off the fused path, else ``fused_body``'s answer."""
    return fused_body(realizations, p) if fused_enabled() else "dense"


def pick_fused_block_rows(r: int, p: int, dtype_bytes: int = 4,
                          vmem_budget: int = 8 * 1024 * 1024,
                          realizations: int = 1) -> int:
    """Row block for the (br, p) slabs of the per-worker row count ``r``:
    the largest multiple of 128 dividing ``r`` whose slab plus its pipeline
    double-buffer fits the VMEM budget, else the whole ``r``.  A divisor
    means no row padding: the grid tiles ``r`` exactly, and either choice
    meets the TPU's (8, 128) block tiling rule.

    With ``realizations`` > 1 (the shared body) the budget first holds the
    double-buffered (R, p) W and accumulator, and each slab row also needs
    an f32 copy and its three bf16 operand splits for the MXU."""
    row = 2 * p * dtype_bytes
    if realizations > 1:
        vmem_budget -= 4 * realizations * p * 4
        row += p * (4 + 3 * 2)
    cap = max(1, vmem_budget // max(1, row))
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


def _shared_body(sx_ref, sy_ref, w_ref, c_ref, o_ref):
    """Grid (R // bR, m, r // br): realization block b, worker i, row
    block j.

    The slab is read once for the bR iterates of the block: the (bR, br)
    transposed residuals U^T = W S_ij X^T - S_ij y^T, weighted by worker
    i's decode weights, then contracted back against the same slab into
    the (bR, p) accumulator, pinned in VMEM like ``_fused_body``'s.  Both
    contractions run on the MXU at ``HIGHEST`` (f32-faithful) precision.
    """
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    hi = lax.Precision.HIGHEST
    sx = sx_ref[...].astype(jnp.float32)                    # (br, p)
    w = w_ref[...].astype(jnp.float32)                      # (bR, p)
    ut = lax.dot_general(w, sx, (((1,), (1,)), ((), ())), precision=hi,
                         preferred_element_type=jnp.float32)   # (bR, br)
    ut = (ut - sy_ref[...].astype(jnp.float32)) * c_ref[...]   # c: (bR, 1)
    o_ref[...] += jnp.dot(ut, sx, precision=hi,
                          preferred_element_type=jnp.float32)  # (bR, p)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def _fused_call(SX: jax.Array, Sy: jax.Array, w: jax.Array, c: jax.Array, *,
                interpret: bool, block_rows: int | None = None) -> jax.Array:
    """One pallas_call: ``_fused_body`` for a (p,) iterate w and (m,)
    weights c, ``_shared_body`` for (R, p) iterates and (R, m) weights."""
    m, r, p = SX.shape
    if w.ndim == 2:
        return _shared_call(SX, Sy, w, c, interpret=interpret,
                            block_rows=block_rows)
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


def _shared_call(SX, Sy, W, C, *, interpret: bool, block_rows: int | None):
    m, r, p = SX.shape
    R = W.shape[0]
    bR = _realization_block(R, p)
    br = block_rows or pick_fused_block_rows(
        r, p, SX.dtype.itemsize, _SHARED_VMEM_BUDGET, realizations=bR)
    # Sy rides as (1, br) rows and C as (bR, 1) columns: the residuals
    # keep the realizations on sublanes and the slab rows on lanes
    out = pl.pallas_call(
        _shared_body,
        grid=(R // bR, m, r // br),
        in_specs=[pl.BlockSpec((None, br, p), lambda b, i, j: (i, j, 0)),
                  pl.BlockSpec((None, 1, br), lambda b, i, j: (i, 0, j)),
                  pl.BlockSpec((bR, p), lambda b, i, j: (b, 0)),
                  pl.BlockSpec((None, bR, 1), lambda b, i, j: (i, b, 0))],
        out_specs=pl.BlockSpec((bR, p), lambda b, i, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((R, p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SHARED_VMEM_LIMIT),
        interpret=interpret,
    )(SX, Sy[:, None, :], W, C.T[:, :, None])
    return out.astype(W.dtype)


@functools.lru_cache(maxsize=8)
def _gradient(interpret: bool, block_rows: int | None):
    """``_fused_call`` with the batching rule that picks its body."""
    call = functools.partial(_fused_call, interpret=interpret,
                             block_rows=block_rows)

    @jax.custom_batching.custom_vmap
    def grad(SX, Sy, w, c):
        return call(SX, Sy, w, c)

    @grad.def_vmap
    def _rule(axis_size, in_batched, SX, Sy, w, c):
        sx_b, sy_b, w_b, c_b = in_batched
        shared = w_b and not (sx_b or sy_b)
        if fused_body(axis_size, SX.shape[-1], shared) == "fused-shared":
            if not c_b:
                c = jnp.broadcast_to(c, (axis_size,) + c.shape)
            return call(SX, Sy, w, c), True
        axes = tuple(0 if b else None for b in in_batched)
        return jax.vmap(call, in_axes=axes)(SX, Sy, w, c), True

    return grad


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
    interpreted elsewhere — the ``coded_reduce.py`` policy).  Under
    ``vmap`` (the batched-trial runners) a batched w over unbatched SX/Sy
    takes the shared-slab body, one pass over SX for every realization;
    any other batching (a batched SX, say) runs the per-realization body
    with the batched axis as a leading grid axis (see ``fused_body``).
    """
    if interpret is None:
        interpret = default_interpret()
    m = SX.shape[0]
    k = jnp.maximum(mask.sum(), 1.0)
    c = (mask * (m / k) / (n * beta)).astype(jnp.float32)          # (m,)
    return _gradient(interpret, block_rows)(SX, Sy, w, c)
