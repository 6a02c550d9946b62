"""Encoded data-parallel optimization (paper §2.1, Algorithms 1-2).

Objective:   f(w) = 1/(2n) ||X w - y||^2 + lam * h(w)
Encoded:     f~(w) = 1/(2 n beta) ||S (X w - y)||^2 + lam * h(w)

Worker i stores (S_i X, S_i y); at iteration t the master combines the
gradients of the fastest ``k`` workers (erasure mask), rescaled by 1/eta.
With the repo convention S^T S = beta I (see core/encoding.py) the masked
gradient estimates  (1/n) X^T (X w - y)  with BRIP error eps.

Everything here is a pure-JAX reference implementation operating on stacked
worker blocks ``(m, rows_per_worker, p)`` — the same functions run unsharded
on CPU (tests, benchmarks) and under pjit with the leading axis mapped onto
the ``data`` mesh axis (launch/).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span as _obs_span

from .encoding import LinearEncoder

__all__ = [
    "EncodedProblem", "make_encoded_problem", "encoded_gradients",
    "masked_gradient", "gd_step", "run_encoded_gd", "prox_l1",
    "run_encoded_proximal", "original_objective",
]


@partial(jax.tree_util.register_dataclass,
         data_fields=("SX", "Sy", "X", "y"),
         meta_fields=("lam", "beta", "n"))
@dataclasses.dataclass
class EncodedProblem:
    """Worker-stacked encoded least-squares problem (a jit-able pytree)."""
    SX: jax.Array      # (m, r, p)   encoded data blocks
    Sy: jax.Array      # (m, r)      encoded responses
    X: jax.Array       # (n, p)      original data (for evaluating f)
    y: jax.Array       # (n,)
    lam: float
    beta: float
    n: int

    @property
    def m(self) -> int:
        return self.SX.shape[0]


@partial(jax.jit, static_argnames=("m", "dtype"))
def _worker_stack(SXy: jax.Array, m: int, dtype) -> tuple:
    """(m, r, p) SX and (m, r) Sy from the encoded [X|y] (m r, p+1): worker
    i owns rows [i r, (i+1) r), the blocks ``encode_partitioned`` cuts."""
    blocks = SXy.reshape(m, -1, SXy.shape[-1])
    return blocks[..., :-1].astype(dtype), blocks[..., -1].astype(dtype)


def make_encoded_problem(X: np.ndarray, y: np.ndarray, enc: LinearEncoder,
                         m: int, lam: float = 0.0,
                         dtype=jnp.float32) -> EncodedProblem:
    """Build the worker-stacked encoded problem from any encoding operator.

    X and y are encoded jointly as one (n, p+1) pass, since the operator
    acts columnwise.  The route follows ``enc.on_device``:

    * Device encoders (``fast-hadamard``) encode in float32 on the device.
      X and y are uploaded once in ``dtype`` (they become ``prob.X`` and
      ``prob.y``), [X|y] is joined and encoded there, and one jitted
      reshape cuts SX and Sy.  Nothing returns to the host and nothing
      blocks: the solve's scan is the next consumer.  With float32 storage
      this is bit for bit the host route's problem, whose f32 -> f64 -> f32
      round trip is exact.
    * Host encoders (dense, block-diagonal) compute ``S @ [X|y]`` in numpy
      float64 and round to ``dtype`` once at the upload; encoding rounded
      inputs would change their result.  Per-worker blocks come from
      ``enc.encode_partitioned`` (one lazy ``worker_block`` per worker by
      default), so S is never materialized and structured encoders only
      touch the input coordinates each worker's rows depend on
      (``input_slice``).
    """
    enc = enc.with_workers(m)
    if enc.on_device:
        with _obs_span("encode:upload"):
            Xd, yd = jnp.asarray(X, dtype), jnp.asarray(y, dtype)
        with _obs_span("encode:transform"):
            SX, Sy = _worker_stack(
                enc.encode(jnp.concatenate([Xd, yd[:, None]], axis=1)),
                m=m, dtype=dtype)
    else:
        with _obs_span("encode:prepare"):
            Xy = np.concatenate([np.asarray(X, np.float64),
                                 np.asarray(y, np.float64)[:, None]], axis=1)
        with _obs_span("encode:transform"):
            blocks = jax.block_until_ready(enc.encode_partitioned(Xy))
        with _obs_span("encode:readback"):
            SXy = np.stack([np.asarray(b, np.float64)
                            for b in blocks])                  # (m, r, p+1)
        with _obs_span("encode:upload"):
            SX = jnp.asarray(SXy[..., :-1], dtype)
            Sy = jnp.asarray(SXy[..., -1], dtype)
            Xd, yd = jnp.asarray(X, dtype), jnp.asarray(y, dtype)
    return EncodedProblem(SX=SX, Sy=Sy, X=Xd, y=yd, lam=float(lam),
                          beta=float(enc.beta), n=X.shape[0])


def original_objective(prob: EncodedProblem, w: jax.Array,
                       h: str = "l2") -> jax.Array:
    """f(w) on the ORIGINAL (uncoded) problem — convergence is measured here."""
    r = prob.X @ w - prob.y
    loss = 0.5 * jnp.vdot(r, r) / prob.n
    if h == "l2":
        reg = 0.5 * jnp.vdot(w, w)
    elif h == "l1":
        reg = jnp.sum(jnp.abs(w))
    elif h == "none":
        reg = 0.0
    else:
        raise ValueError(h)
    return loss + prob.lam * reg


def encoded_gradients(prob: EncodedProblem, w: jax.Array) -> jax.Array:
    """Per-worker gradients of the smooth part, (m, p).

    grad_i = 1/(n beta) (S_i X)^T (S_i X w - S_i y).
    """
    r = jnp.einsum("mrp,p->mr", prob.SX, w) - prob.Sy
    return jnp.einsum("mrp,mr->mp", prob.SX, r) / (prob.n * prob.beta)


def _masked_mean(g: jax.Array, mask: jax.Array) -> jax.Array:
    """(1/eta) sum_{i in A} g_i with eta = k/m — the paper's 1/(2 n eta) scaling.

    On TPU the weighted reduction runs through the fused Pallas combine
    kernel (``kernels/coded_reduce.py``): the (m, p) weighted intermediate
    never round-trips HBM.  Elsewhere the dense einsum is faster than the
    interpreted kernel, so it stays the fallback.
    """
    k = jnp.maximum(mask.sum(), 1.0)
    from repro.kernels.ops import on_tpu
    if on_tpu():
        # weights go in pre-shaped (m, 1): the kernel's sublane layout,
        # built here so no per-step reshape survives into the kernel call
        from repro.kernels.coded_reduce import coded_combine_call
        return coded_combine_call(g, mask[:, None] * (g.shape[0] / k))
    return jnp.einsum("m,mp->p", mask * (g.shape[0] / k), g)


def masked_gradient(prob: EncodedProblem, w: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """Fastest-k aggregation of per-worker encoded gradients."""
    return _masked_mean(encoded_gradients(prob, w), mask)


@partial(jax.jit, static_argnames=("h",))
def gd_step(prob: EncodedProblem, w: jax.Array, mask: jax.Array,
            step_size: float, h: str = "l2") -> jax.Array:
    """Encoded gradient descent step (paper §2.1) with smooth regularizer."""
    g = masked_gradient(prob, w, mask)
    if h == "l2":
        g = g + prob.lam * w
    return w - step_size * g


def run_encoded_gd(prob: EncodedProblem, masks: np.ndarray, step_size: float,
                   w0: jax.Array | None = None, h: str = "l2"):
    """Run GD over a precomputed (T, m) mask schedule; returns (w_T, f-trace).

    Thin wrapper over the scan-fused runner (runtime/runners.py): the whole
    schedule and objective trace stay on device — one compiled program
    instead of one dispatch + host sync per step.  Same math and op order as
    the historical per-step ``gd_step`` loop.
    """
    from repro.runtime.runners import scan_gd
    w = jnp.zeros(prob.SX.shape[-1]) if w0 is None else w0
    w, trace = scan_gd(prob, jnp.asarray(masks, jnp.float32), step_size, w,
                       h=h)
    return w, np.asarray(trace)


def prox_l1(v: jax.Array, thresh: float) -> jax.Array:
    """Soft-thresholding operator (ISTA)."""
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - thresh, 0.0)


@jax.jit
def prox_step(prob: EncodedProblem, w: jax.Array, mask: jax.Array,
              step_size: float) -> jax.Array:
    """Encoded proximal gradient step for l1 regularizer (paper §2.1, Thm 5)."""
    g = masked_gradient(prob, w, mask)
    return prox_l1(w - step_size * g, step_size * prob.lam)


def run_encoded_proximal(prob: EncodedProblem, masks: np.ndarray,
                         step_size: float, w0: jax.Array | None = None):
    """Encoded ISTA over a mask schedule; returns (w_T, f-trace with h=l1).

    Thin wrapper over the scan-fused runner (runtime/runners.py)."""
    from repro.runtime.runners import scan_prox
    w = jnp.zeros(prob.SX.shape[-1]) if w0 is None else w0
    w, trace = scan_prox(prob, jnp.asarray(masks, jnp.float32), step_size, w)
    return w, np.asarray(trace)
