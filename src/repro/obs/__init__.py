"""repro.obs — structured tracing, straggler metrics, profiling hooks.

The observability substrate under every execution layer (DESIGN.md §11):

  * :mod:`repro.obs.trace`   — :class:`TraceRecorder`: per-iteration
    straggler timelines from the ``ClusterEngine`` + host-clock phase spans,
    exported as JSONL and Chrome/Perfetto ``trace_event`` JSON;
  * :mod:`repro.obs.metrics` — counter/gauge/histogram registry + the
    per-cell summarizers (miss-rate, active-set distribution, step-latency
    percentiles, staleness histogram + clamp counts);
  * :mod:`repro.obs.timing`  — the ONE clock/blocking discipline
    (``block`` / ``time_us``) and :class:`CompileWatch`, which splits jit
    compile time out of execute time via ``jax.monitoring``;
  * :mod:`repro.obs.profile` — opt-in ``jax.profiler`` capture and
    device-memory high-water marks;
  * :mod:`repro.obs.sketch`  — O(1)-memory streaming estimators (P²
    quantiles, EWMA, per-worker :class:`DelayTailEstimator` — the
    sensing interface for adaptive redundancy);
  * :mod:`repro.obs.runstore` — indexed run-manifest store (spec hash,
    git sha, backend, artifact paths) every execute/bench run records to;
  * ``python -m repro.obs.diff`` — cross-run regression gate: aligns two
    stored runs (or a bench json vs its committed baseline) cell-by-cell
    and exits non-zero on wall-clock/convergence regressions;
  * ``python -m repro.obs.report`` — text straggler-timeline /
    phase-breakdown reports from a saved trace.

Design rule: with no active recorder every recorder hook is a single
``is None`` check, and a span is a ``jax.profiler.TraceAnnotation``, which
does nothing while no profiler session runs; nothing in ``obs`` blocks on
the device.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      async_metrics, cell_summary, clamp_async_event,
                      fault_metrics, schedule_metrics)
from .profile import memory_high_water, memory_stats, profile_region
from .runstore import (RunStore, begin_experiment, completed_cells,
                       default_store, finish_experiment, provenance,
                       record_cell, record_experiment, runstore_enabled,
                       spec_hash)
from .sketch import DelayTailEstimator, Ewma, P2Quantile, QuantileSketch
from .timing import CompileWatch, block, emit, time_us
from .trace import TraceEvent, TraceRecorder, current_recorder, span

__all__ = [
    "TraceEvent", "TraceRecorder", "current_recorder", "span",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "schedule_metrics", "async_metrics", "fault_metrics", "cell_summary",
    "clamp_async_event",
    "P2Quantile", "QuantileSketch", "Ewma", "DelayTailEstimator",
    "RunStore", "default_store", "runstore_enabled", "provenance",
    "spec_hash", "record_experiment", "begin_experiment",
    "finish_experiment", "record_cell", "completed_cells",
    "CompileWatch", "block", "time_us", "emit",
    "profile_region", "memory_stats", "memory_high_water",
]
