"""Device-resident iteration loops: one ``lax.scan`` fusion per run.

The seed repo ran every strategy as a host loop — one jitted step per
iteration, a host sync to append ``float(objective)`` to a Python list, and a
fresh dispatch per step.  These runners keep the entire (T, m) mask schedule
AND the objective trace on device: a single compiled program scans over the
schedule and returns the full trace.  ``core.data_parallel`` /
``core.model_parallel`` ``run_*`` entry points are now thin wrappers over
these (identical math, identical op order, so traces agree to float rounding).

``scan_async`` is the asynchronous stale-gradient SGD runner: it consumes
a per-arrival event stream from ``runtime.engine`` and maintains a circular
buffer of the last ``staleness_bound + 1`` iterates, indexing it with each
update's staleness — bounded-staleness semantics with per-worker parameter
timestamps, fully fused on device.

``batched_scan_*`` are the Monte-Carlo variants (DESIGN.md §9): ``jax.vmap``
over a leading realization axis inside ONE jit, so "R delay realizations of
one cell" is a single compiled program — every per-step op carries the whole
realization batch instead of dispatching R separate scans.  The carry buffer
is donated (callers hand a fresh (R, ...) stack per call) and ``eval_every``
strides the O(n·p) ``original_objective`` pass: with ``eval_every=s`` the
trace holds f after steps s, 2s, ..., i.e. every s-th entry of the dense
trace.  The jit cache is the cell-level executable cache: every cell of a
comparison matrix with the same (R, T, m, p) shape reuses one executable.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from repro.core.data_parallel import (EncodedProblem, masked_gradient,
                                      original_objective, prox_l1)
from repro.core.model_parallel import LiftedProblem
from repro.kernels.fused_step import (fused_enabled, fused_masked_gradient,
                                      kernel_path)
from repro.obs.trace import span as _obs_span

__all__ = [
    "scan_gd", "scan_prox", "scan_bcd", "scan_async",
    "batched_scan_gd", "batched_scan_prox", "batched_scan_bcd",
    "batched_scan_async",
    "sharded_scan_gd", "sharded_scan_prox", "sharded_scan_async",
    "trials_device_count",
]


def _traced_call(name: str, fn, *args, kernel: str | None = None, **kw):
    """Dispatch a runner inside the program span ``name``.  The span covers
    the dispatch only and never blocks, recorder or not: the device's
    execute time is the profiler trace's to show, and a recorded run takes
    the path of an unrecorded one.  ``kernel``, for the runners that take
    the masked gradient, names its path (``fused_step.kernel_path``) as the
    span's arg of that name."""
    with _obs_span(name, **({} if kernel is None else {"kernel": kernel})):
        return fn(*args, **kw)


# ---------------------------------------------------------------------------
# Shared per-step math (single source of truth for fused + batched runners)
# ---------------------------------------------------------------------------

def _masked_grad(prob: EncodedProblem, w, mask):
    """The per-step masked gradient: the fused Pallas megakernel
    (``kernels/fused_step.py`` — matvec + erasure + combine in one VMEM
    pass; under the batched runners' vmap, one pass for all realizations)
    when ``fused_enabled()`` (TPU default, ``REPRO_FUSED`` override), the
    dense-einsum path of ``core.data_parallel`` everywhere else.  The
    branch is trace-time, so each compiled runner bakes in one path."""
    if fused_enabled():
        return fused_masked_gradient(prob.SX, prob.Sy, w, mask,
                                     n=prob.n, beta=prob.beta)
    return masked_gradient(prob, w, mask)


def _kernel(prob: EncodedProblem, realizations: int) -> str:
    """The masked-gradient path of a runner over ``realizations`` iterates
    a device, for its span's ``kernel`` arg."""
    return kernel_path(realizations, prob.SX.shape[-1])


def _runner_name(base: str) -> str:
    """Obs span name for a runner dispatch; the fused megakernel path is
    called out so traces distinguish it from the dense step."""
    return base + ":fused" if fused_enabled() else base


def _gd_step(prob: EncodedProblem, w, mask, step_size, h: str):
    g = _masked_grad(prob, w, mask)
    if h == "l2":
        g = g + prob.lam * w
    return w - step_size * g


def _prox_step(prob: EncodedProblem, w, mask, step_size):
    g = _masked_grad(prob, w, mask)
    return prox_l1(w - step_size * g, step_size * prob.lam)


# -- sub-k degradation (repro.runtime.faults, DESIGN.md §14) ----------------
#
# ``degrade`` reaches the runners as a static hashable tuple
# ("hold", k_min, shrink) or None; only hold-mode needs runner support (a
# gradient carry), renormalize is the default masked-mean math and backoff
# lives in the engine.  None keeps every runner on its pre-fault trace.

def _degrade_tuple(degrade):
    """Normalize DegradePolicy | tuple | None to the static runner arg."""
    if degrade is None or isinstance(degrade, tuple):
        return degrade
    if getattr(degrade, "mode", None) == "hold":
        return ("hold", int(degrade.k_min or 1), float(degrade.shrink))
    return None


def _hold_gd_step(prob: EncodedProblem, carry, mask, step_size, h: str,
                  k_min: int, shrink: float):
    """GD step on a (w, g_prev) carry: below ``k_min`` survivors the last
    good gradient is reused at ``shrink`` x its previous scale, and the
    shrunk gradient re-enters the carry — consecutive sub-k rounds decay
    geometrically (total held displacement <= step * shrink/(1-shrink) *
    ||g_last||, so a long blackout can never run away on a stale
    direction).  An initial sub-k round holds still (g_prev0 = 0)."""
    w, g_prev = carry
    g_raw = _masked_grad(prob, w, mask)
    if h == "l2":
        g_raw = g_raw + prob.lam * w
    subk = mask.sum() < k_min
    g = jnp.where(subk, shrink * g_prev, g_raw)
    return (w - step_size * g, g)


def _hold_prox_step(prob: EncodedProblem, carry, mask, step_size,
                    k_min: int, shrink: float):
    w, g_prev = carry
    g_raw = _masked_grad(prob, w, mask)
    subk = mask.sum() < k_min
    g = jnp.where(subk, shrink * g_prev, g_raw)
    return (prox_l1(w - step_size * g, step_size * prob.lam), g)


def _async_step(prob: EncodedProblem, carry, ev, step_size, buffer_size: int,
                h: str):
    """One applied update of stale-gradient SGD on the ring-buffer carry."""
    m = prob.SX.shape[0]
    w, buf, head = carry
    i, tau = ev
    w_stale = buf[jnp.mod(head - tau, buffer_size)]
    SXi = prob.SX[i]                       # (r, p) block of worker i
    r = SXi @ w_stale - prob.Sy[i]
    g = (SXi.T @ r) * (m / (prob.n * prob.beta))
    if h == "l2":
        g = g + prob.lam * w_stale
    w_new = w - step_size * g
    head_new = head + 1
    buf = buf.at[jnp.mod(head_new, buffer_size)].set(w_new)
    return (w_new, buf, head_new)


def _strided_scan(step, evalf, carry0, xs, eval_every: int):
    """Scan ``step`` over ``xs`` emitting ``evalf(carry)`` every
    ``eval_every`` steps (a nested scan, so the stride stays on device).
    With ``eval_every=1`` this is the plain fused scan; otherwise the trace
    has length T // eval_every with trace[j] = evalf after step (j+1)*s.
    """
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if eval_every == 1:
        def body(c, x):
            c = step(c, x)
            return c, evalf(c)
        return lax.scan(body, carry0, xs)
    if eval_every < 1 or length % eval_every:
        raise ValueError(f"eval_every={eval_every} must be a positive "
                         f"divisor of the {length}-step schedule")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((length // eval_every, eval_every) + a.shape[1:]),
        xs)

    def outer(c, xb):
        c = lax.scan(lambda c2, x: (step(c2, x), None), c, xb)[0]
        return c, evalf(c)

    return lax.scan(outer, carry0, blocks)


# ---------------------------------------------------------------------------
# Single-realization fused runners
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("h", "eval_every", "degrade"))
def _scan_gd(prob: EncodedProblem, masks: jax.Array, step_size,
             w0: jax.Array, h: str = "l2", eval_every: int = 1,
             degrade=None):
    if degrade is not None:
        _, k_min, shrink = degrade
        (wT, _), trace = _strided_scan(
            lambda c, mask: _hold_gd_step(prob, c, mask, step_size, h,
                                          k_min, shrink),
            lambda c: original_objective(prob, c[0], h=h),
            (w0, jnp.zeros_like(w0)), masks, eval_every)
        return wT, trace
    return _strided_scan(lambda w, mask: _gd_step(prob, w, mask, step_size, h),
                         lambda w: original_objective(prob, w, h=h),
                         w0, masks, eval_every)


def scan_gd(prob: EncodedProblem, masks: jax.Array, step_size,
            w0: jax.Array, h: str = "l2", eval_every: int = 1,
            degrade=None):
    """Encoded GD over a (T, m) mask schedule, fused into one scan.

    Returns (w_T, trace) with trace[t] = f(w_{t+1}) on the original problem —
    the same convention as the legacy per-step loop (``eval_every=s``
    strides the trace like the batched runners).  ``degrade`` selects the
    sub-k behavior (hold-mode gradient carry); None is the default
    renormalized math.
    """
    return _traced_call(_runner_name("runner:gd"), _scan_gd, prob, masks,
                        step_size, w0, h=h, eval_every=eval_every,
                        degrade=_degrade_tuple(degrade),
                        kernel=_kernel(prob, 1))


@partial(jax.jit, static_argnames=("eval_every", "degrade"))
def _scan_prox(prob: EncodedProblem, masks: jax.Array, step_size,
               w0: jax.Array, eval_every: int = 1, degrade=None):
    if degrade is not None:
        _, k_min, shrink = degrade
        (wT, _), trace = _strided_scan(
            lambda c, mask: _hold_prox_step(prob, c, mask, step_size,
                                            k_min, shrink),
            lambda c: original_objective(prob, c[0], h="l1"),
            (w0, jnp.zeros_like(w0)), masks, eval_every)
        return wT, trace
    return _strided_scan(lambda w, mask: _prox_step(prob, w, mask, step_size),
                         lambda w: original_objective(prob, w, h="l1"),
                         w0, masks, eval_every)


def scan_prox(prob: EncodedProblem, masks: jax.Array, step_size,
              w0: jax.Array, eval_every: int = 1, degrade=None):
    """Encoded proximal gradient (ISTA, l1) over a mask schedule."""
    return _traced_call(_runner_name("runner:prox"), _scan_prox, prob, masks,
                        step_size, w0, eval_every=eval_every,
                        degrade=_degrade_tuple(degrade),
                        kernel=_kernel(prob, 1))


# LiftedProblem carries Python callables (phi), so the scan cannot be jitted
# on the problem pytree; cache one compiled runner per (phi_val, phi_grad)
# pair (hashed by closure identity) so repeated runs on the same problem skip
# retracing.  Bounded: each entry pins an XLA executable + the arrays the phi
# closures capture, and fresh phi closures never hit, so old entries must be
# evicted.
@lru_cache(maxsize=8)
def _bcd_runner(phi_val, phi_grad):
    @jax.jit
    def run(XS, masks, step_size, v0):
        def body(v, mask):
            u = jnp.einsum("mnb,mb->mn", XS, v)
            z = u.sum(axis=0)
            gphi = phi_grad(z)
            d = -step_size * jnp.einsum("mnb,n->mb", XS, gphi)
            return v + mask[:, None] * d, phi_val(z)

        vT, trace = lax.scan(body, v0, masks)
        z_final = jnp.einsum("mnb,mb->n", XS, vT)
        return vT, jnp.concatenate([trace, phi_val(z_final)[None]])

    return run


def scan_bcd(prob: LiftedProblem, masks: jax.Array, step_size,
             v0: jax.Array):
    """Encoded BCD (model parallelism) over a mask schedule.

    Trace convention matches the legacy loop: trace[t] = phi(z_t) BEFORE the
    t-th commit, with the final objective appended (length T + 1).
    """
    run = _bcd_runner(prob.phi_val, prob.phi_grad)
    return _traced_call("runner:bcd", run, prob.XS, masks,
                        jnp.asarray(step_size, prob.XS.dtype), v0)


@partial(jax.jit, static_argnames=("buffer_size", "h", "eval_every"))
def _scan_async(prob: EncodedProblem, workers: jax.Array,
                staleness: jax.Array, step_size, w0: jax.Array,
                buffer_size: int, h: str = "l2", eval_every: int = 1):
    buf0 = jnp.tile(w0[None], (buffer_size, 1))
    (w_final, _, _), trace = _strided_scan(
        lambda c, ev: _async_step(prob, c, ev, step_size, buffer_size, h),
        lambda c: original_objective(prob, c[0], h=h),
        (w0, buf0, jnp.int32(0)),
        (workers.astype(jnp.int32), staleness.astype(jnp.int32)), eval_every)
    return w_final, trace


def scan_async(prob: EncodedProblem, workers: jax.Array, staleness: jax.Array,
               step_size, w0: jax.Array, buffer_size: int, h: str = "l2",
               eval_every: int = 1):
    """Asynchronous stale-gradient SGD over a per-arrival event stream.

    workers[u]   — which worker's gradient lands at update u;
    staleness[u] — how many master updates happened since that worker read w.

    The carry holds a ring buffer of the last ``buffer_size`` iterates
    (buffer_size must exceed the engine's staleness bound); update u computes
    worker i's block gradient at the stale iterate and applies it
    immediately.  The per-worker gradient is scaled by m so it is an unbiased
    estimate of the full gradient.
    """
    return _traced_call("runner:async", _scan_async, prob, workers, staleness,
                        step_size, w0, buffer_size=buffer_size, h=h,
                        eval_every=eval_every)


# ---------------------------------------------------------------------------
# Batched-trial runners: vmap over the leading realization axis
# ---------------------------------------------------------------------------

def _step_vector(step_size, R: int):
    """Per-realization step sizes: a scalar broadcasts to all R, a (R,)
    vector (the cell-batching path — C cells x R trials stacked) passes
    through.  Scalar broadcast is value-identical to the old closed-over
    Python float (same f32 rounding in ``w - step * g``)."""
    return jnp.broadcast_to(jnp.asarray(step_size, jnp.float32), (R,))


def _batched_gd(prob: EncodedProblem, masks: jax.Array, step_size,
                w0: jax.Array, h: str = "l2", eval_every: int = 1,
                degrade=None):
    def one(masks_r, w0_r, step_r):
        if degrade is not None:
            _, k_min, shrink = degrade
            (wT, _), trace = _strided_scan(
                lambda c, mask: _hold_gd_step(prob, c, mask, step_r, h,
                                              k_min, shrink),
                lambda c: original_objective(prob, c[0], h=h),
                (w0_r, jnp.zeros_like(w0_r)), masks_r, eval_every)
            return wT, trace
        return _strided_scan(
            lambda w, mask: _gd_step(prob, w, mask, step_r, h),
            lambda w: original_objective(prob, w, h=h),
            w0_r, masks_r, eval_every)

    return jax.vmap(one)(masks, w0, _step_vector(step_size, masks.shape[0]))


@partial(jax.jit, static_argnames=("h", "eval_every", "degrade"),
         donate_argnums=(3,))
def _batched_scan_gd(prob: EncodedProblem, masks: jax.Array, step_size,
                     w0: jax.Array, h: str = "l2", eval_every: int = 1,
                     degrade=None):
    return _batched_gd(prob, masks, step_size, w0, h, eval_every, degrade)


# R == 1 wrappers: the squeeze/unsqueeze happens INSIDE one traced program
# (free at runtime) — host-side masks[0] / w[None] reshapes around _scan_gd
# would cost several extra dispatches per call, eating the win
@partial(jax.jit, static_argnames=("h", "eval_every", "degrade"),
         donate_argnums=(3,))
def _scan_gd_r1(prob: EncodedProblem, masks: jax.Array, step_size,
                w0: jax.Array, h: str = "l2", eval_every: int = 1,
                degrade=None):
    w, tr = _scan_gd(prob, masks[0], jnp.asarray(step_size).reshape(()),
                     w0[0], h=h, eval_every=eval_every, degrade=degrade)
    return w[None], tr[None]


@partial(jax.jit, static_argnames=("eval_every", "degrade"),
         donate_argnums=(3,))
def _scan_prox_r1(prob: EncodedProblem, masks: jax.Array, step_size,
                  w0: jax.Array, eval_every: int = 1, degrade=None):
    w, tr = _scan_prox(prob, masks[0], jnp.asarray(step_size).reshape(()),
                       w0[0], eval_every=eval_every, degrade=degrade)
    return w[None], tr[None]


def batched_scan_gd(prob: EncodedProblem, masks: jax.Array, step_size,
                    w0: jax.Array, h: str = "l2", eval_every: int = 1,
                    degrade=None):
    """R realizations of encoded GD in one compiled program.

    masks: (R, T, m) stacked schedules; w0: (R, p) per-realization starts
    (donated — hand a fresh stack per call).  ``step_size`` may be a scalar
    or a per-realization (R,) vector.  Returns (w (R, p),
    trace (R, T // eval_every)) with trace[r, j] = f(w after step
    (j+1)*eval_every) of realization r.

    R == 1 routes through the single-trial scan (no vmap axis): batching a
    lone realization only adds overhead (BENCH_trials.json showed 0.79x),
    and the result is identical by construction.
    """
    degrade = _degrade_tuple(degrade)
    if masks.shape[0] == 1:
        return _traced_call(_runner_name("runner:gd"), _scan_gd_r1, prob,
                            masks, step_size, w0, h=h,
                            eval_every=eval_every, degrade=degrade,
                            kernel=_kernel(prob, 1))
    return _traced_call(_runner_name("runner:batched_gd"), _batched_scan_gd,
                        prob, masks, step_size, w0, h=h,
                        eval_every=eval_every, degrade=degrade,
                        kernel=_kernel(prob, masks.shape[0]))


def _batched_prox(prob: EncodedProblem, masks: jax.Array, step_size,
                  w0: jax.Array, eval_every: int = 1, degrade=None):
    def one(masks_r, w0_r, step_r):
        if degrade is not None:
            _, k_min, shrink = degrade
            (wT, _), trace = _strided_scan(
                lambda c, mask: _hold_prox_step(prob, c, mask, step_r,
                                                k_min, shrink),
                lambda c: original_objective(prob, c[0], h="l1"),
                (w0_r, jnp.zeros_like(w0_r)), masks_r, eval_every)
            return wT, trace
        return _strided_scan(
            lambda w, mask: _prox_step(prob, w, mask, step_r),
            lambda w: original_objective(prob, w, h="l1"),
            w0_r, masks_r, eval_every)

    return jax.vmap(one)(masks, w0, _step_vector(step_size, masks.shape[0]))


@partial(jax.jit, static_argnames=("eval_every", "degrade"),
         donate_argnums=(3,))
def _batched_scan_prox(prob: EncodedProblem, masks: jax.Array, step_size,
                       w0: jax.Array, eval_every: int = 1, degrade=None):
    return _batched_prox(prob, masks, step_size, w0, eval_every, degrade)


def batched_scan_prox(prob: EncodedProblem, masks: jax.Array, step_size,
                      w0: jax.Array, eval_every: int = 1, degrade=None):
    """R realizations of encoded ISTA in one compiled program (see
    ``batched_scan_gd`` for the axis/donation/eval_every/R==1
    conventions)."""
    degrade = _degrade_tuple(degrade)
    if masks.shape[0] == 1:
        return _traced_call(_runner_name("runner:prox"), _scan_prox_r1,
                            prob, masks, step_size, w0,
                            eval_every=eval_every, degrade=degrade,
                            kernel=_kernel(prob, 1))
    return _traced_call(_runner_name("runner:batched_prox"),
                        _batched_scan_prox, prob, masks, step_size, w0,
                        eval_every=eval_every, degrade=degrade,
                        kernel=_kernel(prob, masks.shape[0]))


@lru_cache(maxsize=8)
def _bcd_batched_runner(phi_val, phi_grad):
    @partial(jax.jit, static_argnames=("eval_every",), donate_argnums=(3,))
    def run(XS, masks, step_size, v0, eval_every=1):
        def step(v, mask):
            z = jnp.einsum("mnb,mb->mn", XS, v).sum(axis=0)
            d = -step_size * jnp.einsum("mnb,n->mb", XS, phi_grad(z))
            return v + mask[:, None] * d

        def evalf(v):
            return phi_val(jnp.einsum("mnb,mb->n", XS, v))

        def one(masks_r, v0_r):
            return _strided_scan(step, evalf, v0_r, masks_r, eval_every)

        return jax.vmap(one)(masks, v0)

    return run


def batched_scan_bcd(prob: LiftedProblem, masks: jax.Array, step_size,
                     v0: jax.Array, eval_every: int = 1):
    """R realizations of encoded BCD in one compiled program.

    masks: (R, T, m); v0: (R, m, b) (donated).  Unlike ``scan_bcd``'s
    legacy pre-commit trace, the batched trace is POST-commit:
    trace[r, j] = phi(z after commit (j+1)*eval_every), i.e. with
    eval_every=1 it equals ``scan_bcd``'s trace[1:] — the slice every
    strategy reports anyway.

    R == 1 (at eval_every=1, where the trace conventions coincide) routes
    through the single-trial scan like ``batched_scan_gd``.
    """
    if masks.shape[0] == 1 and eval_every == 1:
        v, tr = scan_bcd(prob, masks[0], step_size, v0[0])
        return v[None], tr[None, 1:]
    run = _bcd_batched_runner(prob.phi_val, prob.phi_grad)
    return _traced_call("runner:batched_bcd", run, prob.XS, masks,
                        jnp.asarray(step_size, prob.XS.dtype), v0,
                        eval_every=eval_every)


def _batched_async(prob: EncodedProblem, workers: jax.Array,
                   staleness: jax.Array, step_size, w0: jax.Array,
                   buffer_size: int = 1, h: str = "l2", eval_every: int = 1):
    def one(workers_r, staleness_r, w0_r):
        buf0 = jnp.tile(w0_r[None], (buffer_size, 1))
        (w_final, _, _), trace = _strided_scan(
            lambda c, ev: _async_step(prob, c, ev, step_size, buffer_size, h),
            lambda c: original_objective(prob, c[0], h=h),
            (w0_r, buf0, jnp.int32(0)),
            (workers_r.astype(jnp.int32), staleness_r.astype(jnp.int32)),
            eval_every)
        return w_final, trace

    return jax.vmap(one)(workers, staleness, w0)


@partial(jax.jit, static_argnames=("buffer_size", "h", "eval_every"),
         donate_argnums=(4,))
def _batched_scan_async(prob: EncodedProblem, workers: jax.Array,
                        staleness: jax.Array, step_size, w0: jax.Array,
                        buffer_size: int, h: str = "l2", eval_every: int = 1):
    return _batched_async(prob, workers, staleness, step_size, w0,
                          buffer_size, h, eval_every)


def batched_scan_async(prob: EncodedProblem, workers: jax.Array,
                       staleness: jax.Array, step_size, w0: jax.Array,
                       buffer_size: int, h: str = "l2", eval_every: int = 1):
    """R realizations of async stale-gradient SGD in one compiled program.

    workers/staleness: (R, U) stacked event streams; w0: (R, p) (donated).
    Returns (w (R, p), trace (R, U // eval_every)).  R == 1 routes through
    the single-trial scan (see ``batched_scan_gd``).
    """
    if workers.shape[0] == 1:
        w, tr = _traced_call("runner:async", _scan_async, prob, workers[0],
                             staleness[0], step_size, w0[0],
                             buffer_size=buffer_size, h=h,
                             eval_every=eval_every)
        return w[None], tr[None]
    return _traced_call("runner:batched_async", _batched_scan_async, prob,
                        workers, staleness, step_size, w0,
                        buffer_size=buffer_size, h=h, eval_every=eval_every)


# ---------------------------------------------------------------------------
# Sharded-trial runners: shard_map over a 'trials' mesh axis (DESIGN.md §10)
# ---------------------------------------------------------------------------

def trials_device_count(trials: int) -> int:
    """Devices the 'trials' mesh axis can use for R realizations: every
    local device when R divides evenly across them, else 1 (= the vmap
    fallback — sharding cannot help a single device, and a ragged split
    would need padding that changes the executable shape)."""
    ndev = len(jax.devices())
    return ndev if ndev > 1 and trials % ndev == 0 else 1


@lru_cache(maxsize=16)
def _sharded_fn(kind: str, ndev: int, h: str, eval_every: int,
                buffer_size: int, degrade=None):
    """One compiled shard_map executable per (runner kind, mesh size,
    static config).  Each mesh shard runs the plain vmapped body over its
    R/ndev local realizations — realizations are independent, so there are
    no collectives and per-realization results match the vmap placement
    (bitwise in practice; the suite enforces 1e-5)."""
    mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("trials",))
    P, Pt = PartitionSpec(), PartitionSpec("trials")
    if kind == "gd":
        impl = partial(_batched_gd, h=h, eval_every=eval_every,
                       degrade=degrade)
        in_specs = (P, Pt, P, Pt)
    elif kind == "prox":
        impl = partial(_batched_prox, eval_every=eval_every,
                       degrade=degrade)
        in_specs = (P, Pt, P, Pt)
    elif kind == "async":
        impl = partial(_batched_async, buffer_size=buffer_size, h=h,
                       eval_every=eval_every)
        in_specs = (P, Pt, Pt, P, Pt)
    else:
        raise KeyError(f"unknown sharded runner kind '{kind}'")
    # check_vma=False: the Pallas kernels on the step path mix replicated
    # operands (SX) with per-realization ones (w) inside their bodies,
    # which the varying-axes check cannot follow through a pallas_call
    return jax.jit(jax.shard_map(impl, mesh=mesh, in_specs=in_specs,
                                 out_specs=(Pt, Pt), check_vma=False))


def sharded_scan_gd(prob: EncodedProblem, masks: jax.Array, step_size,
                    w0: jax.Array, h: str = "l2", eval_every: int = 1,
                    degrade=None):
    """``batched_scan_gd`` with the realization axis sharded across the
    local device mesh.  Returns (w, trace, ndev); ndev == 1 means the vmap
    fallback ran (single device, or R not divisible by the device count).
    """
    degrade = _degrade_tuple(degrade)
    ndev = trials_device_count(masks.shape[0])
    if ndev == 1:
        w, tr = batched_scan_gd(prob, masks, step_size, w0, h=h,
                                eval_every=eval_every, degrade=degrade)
        return w, tr, 1
    fn = _sharded_fn("gd", ndev, h, eval_every, 0, degrade)
    w, tr = _traced_call("runner:sharded_gd", fn, prob, masks,
                         jnp.asarray(step_size, jnp.float32), w0,
                         kernel=_kernel(prob, masks.shape[0] // ndev))
    return w, tr, ndev


def sharded_scan_prox(prob: EncodedProblem, masks: jax.Array, step_size,
                      w0: jax.Array, eval_every: int = 1, degrade=None):
    """``batched_scan_prox`` sharded over the trials mesh axis (see
    ``sharded_scan_gd``)."""
    degrade = _degrade_tuple(degrade)
    ndev = trials_device_count(masks.shape[0])
    if ndev == 1:
        w, tr = batched_scan_prox(prob, masks, step_size, w0,
                                  eval_every=eval_every, degrade=degrade)
        return w, tr, 1
    fn = _sharded_fn("prox", ndev, "l1", eval_every, 0, degrade)
    w, tr = _traced_call("runner:sharded_prox", fn, prob, masks,
                         jnp.asarray(step_size, jnp.float32), w0,
                         kernel=_kernel(prob, masks.shape[0] // ndev))
    return w, tr, ndev


def sharded_scan_async(prob: EncodedProblem, workers: jax.Array,
                       staleness: jax.Array, step_size, w0: jax.Array,
                       buffer_size: int, h: str = "l2", eval_every: int = 1):
    """``batched_scan_async`` sharded over the trials mesh axis (see
    ``sharded_scan_gd``)."""
    ndev = trials_device_count(workers.shape[0])
    if ndev == 1:
        w, tr = batched_scan_async(prob, workers, staleness, step_size, w0,
                                   buffer_size, h=h, eval_every=eval_every)
        return w, tr, 1
    fn = _sharded_fn("async", ndev, h, eval_every, buffer_size)
    w, tr = _traced_call("runner:sharded_async", fn, prob, workers, staleness,
                         jnp.asarray(step_size, jnp.float32), w0)
    return w, tr, ndev
