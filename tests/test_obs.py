"""repro.obs (DESIGN.md §11): recorder capture + JSONL/Perfetto round-trips,
deterministic event streams, per-realization lanes, metrics vs a
hand-computed schedule, async staleness clamping at the trace boundary,
the CompileWatch compile/execute split, the disabled-path no-op guarantee
(structure + overhead guard), the report CLI and the experiments wiring
(ObsAxis gating, --trace/--metrics-out end-to-end)."""
import contextlib
import csv
import json
import time

import numpy as np
import pytest

from repro.obs import (CompileWatch, Counter, Gauge, Histogram,
                       MetricsRegistry, TraceRecorder, async_metrics,
                       cell_summary, clamp_async_event, current_recorder,
                       schedule_metrics, span)
from repro.obs.report import main as report_main, phase_breakdown
from repro.runtime import ClusterEngine, FastestK, make_delay_model
from repro.runtime.engine import AsyncTrace, IterationEvent, Schedule

M, K, T = 8, 6, 12


def _engine(seed=0, m=M):
    return ClusterEngine(make_delay_model("bimodal"), m, seed=seed)


def _hand_schedule():
    """3 iterations x 3 workers with known miss rates and latencies."""
    masks = np.asarray([[1, 1, 0], [1, 0, 1], [1, 1, 1]], dtype=np.float32)
    times = np.asarray([1.0, 2.5, 3.0])
    events, now = [], 0.0
    for t in range(3):
        active = np.flatnonzero(masks[t])
        events.append(IterationEvent(
            t=t, start=now, commit=float(times[t]), active=active,
            arrivals=np.full(3, float(times[t]))))
        now = float(times[t])
    return Schedule(3, masks, times, tuple(events))


# ---------------------------------------------------------------------------
# recorder capture
# ---------------------------------------------------------------------------

def test_disabled_path_is_noop():
    assert current_recorder() is None
    assert isinstance(span("x", a=1), contextlib.nullcontext)
    rec = TraceRecorder()
    _engine().sample_schedule(T, FastestK(K))
    assert rec.events() == []          # nothing recorded while inactive


def test_engine_schedule_capture_and_determinism():
    def capture():
        rec = TraceRecorder()
        with rec.activate():
            _engine().sample_schedule(T, FastestK(K))
        return rec
    a, b = capture(), capture()
    iters = a.iteration_events()
    assert len(iters) == T
    assert len(a.worker_events()) == T * M
    assert [e.name for e in a.spans()] == ["sample-schedule"]
    # fixed seed => bit-identical event streams
    assert [e.to_dict() for e in a.events() if e.kind != "span"] == \
        [e.to_dict() for e in b.events() if e.kind != "span"]
    # iter durations/commits mirror the schedule's wall-clock accounting
    sched = _engine().sample_schedule(T, FastestK(K))
    np.testing.assert_allclose([e.ts + e.dur for e in iters], sched.times)


def test_batched_lanes_one_per_realization():
    R = 3
    rec = TraceRecorder()
    with rec.activate():
        _engine().sample_schedules(T, FastestK(K), R)
    lanes = {e.realization for e in rec.iteration_events()}
    assert lanes == set(range(R))
    for r in range(R):
        assert sum(e.realization == r for e in rec.iteration_events()) == T


def test_trial_engines_land_on_their_lane():
    """Host-loop harnesses (engine.trial(r)) must hit the same lanes as the
    batched samplers."""
    eng = _engine()
    rec = TraceRecorder()
    with rec.activate():
        for r in range(3):
            eng.trial(r).sample_schedule(T, FastestK(K))
    assert {e.realization for e in rec.iteration_events()} == {0, 1, 2}


def test_async_capture_counts():
    rec = TraceRecorder()
    with rec.activate():
        tr = _engine().sample_async(30, 4)
    ups = [e for e in rec.events() if e.kind == "update"]
    assert len(ups) == tr.updates == 30
    summaries = [e for e in rec.events() if e.name == "async-summary"]
    assert len(summaries) == 1
    assert summaries[0].args["dropped"] == tr.dropped
    assert summaries[0].args["staleness_clamped"] == 0


# ---------------------------------------------------------------------------
# round-trips
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_and_perfetto(tmp_path):
    rec = TraceRecorder(meta={"suite": "test"})
    with rec.activate(), rec.cell("cellA"):
        with rec.span("encode", strategy="coded-gd"):
            pass
        _engine().sample_schedule(4, FastestK(K))
    path = tmp_path / "trace.jsonl"
    rec.to_jsonl(str(path))
    back = TraceRecorder.load(str(path))
    assert back.meta == {"suite": "test"}
    assert [e.to_dict() for e in back.events()] == \
        [e.to_dict() for e in rec.events()]

    pf = tmp_path / "trace.perfetto.json"
    back.to_perfetto(str(pf))
    doc = json.loads(pf.read_text())
    tev = doc["traceEvents"]
    names = {e.get("args", {}).get("name") for e in tev if e["ph"] == "M"}
    assert "host (phase spans)" in names
    assert "sim cellA [r0]" in names
    assert f"worker:{M - 1}" in names
    # complete events carry microsecond timestamps; at least the spans + iters
    assert sum(e["ph"] == "X" for e in tev) >= 1 + 4 + 4 * M


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    reg.gauge("m").set(8)
    reg.histogram("lat").observe_many([1.0, 2.0, 3.0, 4.0])
    s = reg.summary()
    assert s["hits"] == 3 and s["m"] == 8
    assert s["lat"]["count"] == 4 and s["lat"]["mean"] == 2.5
    assert s["lat"]["p50"] == 2.5
    with pytest.raises(TypeError):
        reg.counter("m")


def test_schedule_metrics_hand_computed():
    sm = schedule_metrics([_hand_schedule()])
    assert sm["iterations"] == 3 and sm["workers"] == 3
    np.testing.assert_allclose(sm["miss_rate"], [0.0, 1 / 3, 1 / 3])
    np.testing.assert_allclose(sm["mean_miss_rate"], 2 / 9)
    np.testing.assert_allclose(sm["max_miss_rate"], 1 / 3)
    assert sm["active_size"]["hist"] == {"2": 2, "3": 1}
    # barrier latencies diff([1.0, 2.5, 3.0], prepend 0) = [1.0, 1.5, 0.5]
    lat = sm["step_latency_s"]
    assert lat["count"] == 3
    np.testing.assert_allclose(lat["p50"], 1.0)
    np.testing.assert_allclose([lat["min"], lat["max"]], [0.5, 1.5])


def test_async_metrics_engine_trace_never_clamps():
    tr = _engine().sample_async(40, 5)
    am = async_metrics([tr])
    assert am["updates"] == 40
    assert am["staleness_clamped"] == 0
    assert am["dropped"] == tr.dropped
    assert am["staleness"]["max"] <= 5


def test_async_clamp_on_inconsistent_trace():
    # update u=1 claims read_version 5 with staleness 0: rv + tau != u and
    # rv >= total => must be snapped into range and counted
    bad = AsyncTrace(
        m=2, workers=np.asarray([0, 1], dtype=np.int32),
        staleness=np.asarray([0, 0], dtype=np.int32),
        read_versions=np.asarray([0, 5], dtype=np.int32),
        times=np.asarray([0.1, 0.2]), dropped=0)
    assert clamp_async_event(1, 0, 5, 2) == (0, 1, True)
    am = async_metrics([bad])
    assert am["staleness_clamped"] == 1
    rec = TraceRecorder()
    rec.record_async(bad)
    summary = [e for e in rec.events() if e.name == "async-summary"][0]
    assert summary.args["staleness_clamped"] == 1
    # the exported event stream carries the clamped values
    ups = [e for e in rec.events() if e.kind == "update"]
    assert ups[1].args == {"staleness": 0, "read_version": 1}


def test_cell_summary_dispatches_both_kinds():
    rec = TraceRecorder()
    with rec.activate():
        _engine().sample_schedule(5, FastestK(K))
        _engine().sample_async(10, 3)
    cs = cell_summary(rec.sources_since(0))
    assert cs["schedule"]["iterations"] == 5
    assert cs["async"]["updates"] == 10


# ---------------------------------------------------------------------------
# timing / compile split
# ---------------------------------------------------------------------------

def test_compile_watch_splits_compile_from_execute():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, c):
        return jnp.sin(x) * c

    x = jnp.arange(101.0)
    with CompileWatch() as cold:
        jax.block_until_ready(f(x, 2.0))
    assert cold.compiles >= 1
    assert cold.compile_s > 0.0
    with CompileWatch() as warm:
        jax.block_until_ready(f(x, 2.0))
    assert warm.compiles == 0 and warm.compile_s == 0.0
    for cw in (cold, warm):
        assert cw.execute_s >= 0.0
        np.testing.assert_allclose(cw.compile_s + cw.execute_s, cw.total_s)


def test_tracing_overhead_disabled_under_5_percent():
    """With no active recorder the hooks are one is-None check; budget 5%
    (plus absolute slack for timer noise) on an engine-sampling loop."""
    eng = _engine()

    def work():
        eng.sample_schedule(T, FastestK(K))

    def best_of(n=7):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            work()
            best = min(best, time.perf_counter() - t0)
        return best

    best_of()                          # warm caches / allocator
    t_off = best_of()
    assert current_recorder() is None
    # absolute slack on BOTH sides: sub-millisecond work drifts either way
    # on a busy host, and a faster re-measure is not an overhead signal
    assert t_off * 0.95 - 2e-3 < best_of() < t_off * 1.05 + 2e-3


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def test_report_renders_phases_and_lanes(tmp_path, capsys):
    rec = TraceRecorder()
    with rec.activate(), rec.cell("ridge/codedxbimodal"):
        with rec.span("encode"):
            pass
        _engine().sample_schedule(6, FastestK(K))
        _engine().sample_async(8, 3)
    path = tmp_path / "t.jsonl"
    rec.to_jsonl(str(path))
    text = report_main([str(path), "--max-steps", "4"])
    assert "phase breakdown" in text
    assert "straggler timeline — cell=ridge/codedxbimodal" in text
    assert "per-worker miss-rate" in text
    assert "staleness histogram" in text
    rows = phase_breakdown(rec.events())
    assert [r[0] for r in rows][:1] == ["encode"] or \
        "encode" in [r[0] for r in rows]


# ---------------------------------------------------------------------------
# experiments wiring (ObsAxis gating + CLI flags)
# ---------------------------------------------------------------------------

def _small_spec(obs=None, strategies=("coded-gd",)):
    from repro.experiments import (DelayAxis, ExperimentSpec, ObsAxis,
                                   PlacementAxis, ProblemAxis, StrategyAxis,
                                   TrialsAxis)
    return ExperimentSpec(
        problems=(ProblemAxis.synthetic(64, 16),),
        strategies=tuple(StrategyAxis(s) for s in strategies),
        delays=DelayAxis(delays=("bimodal",), m=M),
        trials=TrialsAxis(trials=2), placement=PlacementAxis(mode="vmap"),
        steps=8, obs=obs if obs is not None else ObsAxis())


def test_obs_axis_gates_record_fields():
    from repro.experiments import ObsAxis
    from repro.experiments.execute import run
    plain = run(_small_spec())
    assert plain.recorder is None
    for key in ("obs", "compile_s", "execute_s", "host_s", "compiles"):
        assert key not in plain.records[0]

    observed = run(_small_spec(obs=ObsAxis(metrics=True)))
    assert observed.recorder is not None
    rec = observed.records[0]
    assert rec["compiles"] >= 0
    np.testing.assert_allclose(rec["compile_s"] + rec["execute_s"],
                               rec["host_s"], rtol=1e-6)
    sm = rec["obs"]["schedule"]
    assert sm["workers"] == M and sm["iterations"] == 2 * 8
    # stripping the obs keys recovers the byte-identical default record
    stripped = {k: v for k, v in rec.items() if k not in
                ("obs", "compile_s", "execute_s", "host_s", "compiles")}
    assert stripped == plain.records[0]


def test_obs_trace_export_from_execute(tmp_path):
    from repro.experiments import ObsAxis
    from repro.experiments.execute import run
    prefix = tmp_path / "exp" / "trace"
    result = run(_small_spec(obs=ObsAxis(trace=str(prefix))))
    loaded = TraceRecorder.load(str(prefix) + ".jsonl")
    iters = loaded.iteration_events()
    assert len(iters) == 2 * 8
    assert {e.cell for e in iters} == {"coded-gdxbimodal"}
    assert {e.realization for e in iters} == {0, 1}
    doc = json.loads((tmp_path / "exp" / "trace.perfetto.json").read_text())
    assert len(doc["traceEvents"]) > 0
    assert result.recorder is not None


def test_metrics_csv_writer(tmp_path):
    from repro.experiments import ObsAxis, write_metrics_csv
    from repro.experiments.execute import run
    result = run(_small_spec(obs=ObsAxis(metrics=True),
                             strategies=("coded-gd", "async")))
    path = tmp_path / "metrics.csv"
    write_metrics_csv(result.records, str(path))
    rows = list(csv.DictReader(path.open()))
    assert len(rows) == 2
    sync = next(r for r in rows if r["strategy"] == "coded-gd")
    assert float(sync["mean_miss_rate"]) == pytest.approx(1 - K / M, abs=0.2)
    assert float(sync["compile_s"]) >= 0.0
    asyn = next(r for r in rows if r["strategy"] == "async")
    assert asyn["staleness_mean"] != ""
    assert asyn["staleness_clamped"] == "0"


def test_cli_trace_and_metrics_flags(tmp_path):
    from repro.experiments.run import main
    out = tmp_path / "out"
    trace = tmp_path / "trace"
    metrics = tmp_path / "metrics.csv"
    main(["--strategies", "coded-gd", "--delays", "bimodal", "--n", "64",
          "--p", "16", "--m", str(M), "--steps", "6", "--trials", "2",
          "--out", str(out), "--trace", str(trace),
          "--metrics-out", str(metrics)])
    assert (out / "experiments.json").exists()
    n_iter = sum(1 for line in open(str(trace) + ".jsonl")
                 if json.loads(line).get("kind") == "iter")
    assert n_iter == 2 * 6
    json.loads(open(str(trace) + ".perfetto.json").read())
    assert len(list(csv.DictReader(metrics.open()))) == 1


def test_workload_matrix_obs_kwarg():
    from repro.experiments import ObsAxis
    from repro.workloads.runner import run_workload_matrix
    records = run_workload_matrix(
        ["ridge"], ["uncoded"], steps=6, trials=2,
        obs=ObsAxis(metrics=True))
    assert "obs" in records[0] and "compile_s" in records[0]
    plain = run_workload_matrix(["ridge"], ["uncoded"], steps=6, trials=2)
    assert "obs" not in plain[0]
