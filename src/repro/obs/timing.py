"""The ONE clock/blocking discipline (DESIGN.md §11).

Every timed region in the repo — benchmarks, the experiment executor, the
obs span recorder — goes through these helpers so numbers are comparable:

  * :func:`block`      — ``jax.block_until_ready`` on EVERY output leaf of a
    pytree (blocking only the first leaf lets later dispatches overlap the
    clock and under-reports);
  * :func:`time_us`    — mean microseconds per call, blocking INSIDE the
    timed loop (ported from ``benchmarks/common.py``, which now re-exports
    these);
  * :class:`CompileWatch` — splits jit compile time out of a timed region
    via ``jax.monitoring``'s compile-duration events, so ``execute_s`` never
    silently includes a retrace/recompile and cache misses are countable.
"""
from __future__ import annotations

import time

import jax

__all__ = ["block", "time_us", "emit", "CompileWatch"]


def block(out):
    """``jax.block_until_ready`` on every leaf of ``out``; host values pass
    through.  A device error surfacing here propagates."""
    return jax.block_until_ready(out)


def time_us(fn, *args, iters: int = 5, warmup: int = 1, **kw) -> float:
    """Mean microseconds per call; blocks on device outputs INSIDE the timed
    loop (blocking only after the final call lets earlier dispatches overlap
    and under-reports per-iteration time)."""
    for _ in range(warmup):
        block(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(iters):
        block(fn(*args, **kw))
    return (time.perf_counter() - t0) / iters * 1e6


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """One benchmark CSV line on stdout (shared by every ``benchmarks.*``)."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Compile-time accounting
# ---------------------------------------------------------------------------

# jax.monitoring duration events that make up one jit compilation; the
# backend_compile event fires exactly once per XLA compilation, so it doubles
# as the recompile counter.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_KEYS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _COMPILE_EVENT,
)

_STATE = {"secs": 0.0, "compiles": 0, "registered": False}


def _on_event(event: str, duration_secs: float, **kw) -> None:
    if event in _COMPILE_KEYS:
        _STATE["secs"] += float(duration_secs)
        if event == _COMPILE_EVENT:
            _STATE["compiles"] += 1


def _ensure_listener() -> None:
    """Register the (process-global, idempotent) compile listener."""
    if not _STATE["registered"]:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _STATE["registered"] = True


class CompileWatch:
    """Measure a region, splitting jit compile time from execute time.

    ``with CompileWatch() as cw: ...`` leaves ``cw.total_s`` (wall),
    ``cw.compile_s`` (trace + lower + XLA compile seconds inside the
    region), ``cw.execute_s`` (the remainder) and ``cw.compiles`` (number
    of fresh XLA compilations — 0 means every dispatch hit the jit cache).
    The split comes from ``jax.monitoring`` events, so no warm-up call or
    AOT ``lower().compile()`` is needed and module-level jit caches keep
    working as the cross-cell executable cache.
    """

    def __enter__(self) -> "CompileWatch":
        _ensure_listener()
        self._s0 = _STATE["secs"]
        self._n0 = _STATE["compiles"]
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.total_s = time.perf_counter() - self._t0
        # clamp: monitoring durations are measured independently of our wall
        # clock, so rounding can nudge the sum past total on tiny regions
        self.compile_s = min(max(_STATE["secs"] - self._s0, 0.0),
                             self.total_s)
        self.compiles = _STATE["compiles"] - self._n0
        self.execute_s = max(self.total_s - self.compile_s, 0.0)
