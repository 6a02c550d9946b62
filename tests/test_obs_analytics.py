"""Cross-run analytics (DESIGN.md §13): P² sketch accuracy at O(1)
memory, delay-tail estimators feeding the metrics CSV, run-store
manifest round-trips, and the diff CLI's regression gate (exit 0 on
identical runs, non-zero on an injected 2x slowdown)."""
import copy
import csv
import json
import os

import numpy as np
import pytest

from repro.obs.diff import main as diff_main
from repro.obs.runstore import (RunStore, provenance, record_experiment,
                                spec_hash)
from repro.obs.sketch import (DelayTailEstimator, Ewma, P2Quantile,
                              QuantileSketch)

# ---------------------------------------------------------------------------
# sketches
# ---------------------------------------------------------------------------


def test_sketch_exact_below_buffer():
    s = QuantileSketch(buffer_size=64)
    vals = [3.0, 1.0, 2.0, 5.0, 4.0]
    s.observe_many(vals)
    assert not s.spilled
    assert s.quantile(50) == np.percentile(vals, 50)
    assert s.summary()["count"] == 5
    assert "approx" not in s.summary()


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_sketch_within_1pct_of_exact_on_1e6_samples(dist):
    """The ISSUE-8 accuracy contract: p50/p95/p99 within 1% of exact
    np.percentile on 10^6 samples, while holding O(1) state (the raw
    buffer is dropped at the spill)."""
    rng = np.random.default_rng(7)
    n = 1_000_000 if dist == "lognormal" else 200_000
    x = {"lognormal": lambda: rng.lognormal(0.0, 1.0, n),
         "uniform": lambda: rng.random(n),
         "exponential": lambda: rng.exponential(1.0, n)}[dist]()
    s = QuantileSketch(buffer_size=4096)
    for chunk in np.array_split(x, 50):
        s.observe_many(chunk)
    assert s.spilled and s._buf is None         # O(1): no samples retained
    assert all(est._init is None for est in s._p2.values())
    for q in (50, 95, 99):
        exact = np.percentile(x, q)
        rel = abs(s.quantile(q) - exact) / abs(exact)
        assert rel < 0.01, f"p{q}: {s.quantile(q)} vs {exact} ({rel:.2%})"
    assert s.summary()["approx"] is True
    assert s.count == n
    np.testing.assert_allclose(s.summary()["mean"], x.mean(), rtol=1e-6)


def test_p2_small_sample_exact():
    p2 = P2Quantile(0.5)
    for v in [1.0, 9.0, 3.0]:
        p2.observe(v)
    assert p2.value == 3.0                     # exact below 5 observations


def test_sketch_untracked_percentile_after_spill_raises():
    s = QuantileSketch(percentiles=(50,), buffer_size=8)
    s.observe_many(range(20))
    assert s.spilled
    assert s.quantile(50) is not None
    with pytest.raises(KeyError):
        s.quantile(95)


def test_ewma_converges():
    e = Ewma(alpha=0.5)
    assert e.value is None
    e.update(10.0)
    assert e.value == 10.0                     # first update is exact
    for _ in range(40):
        e.update(2.0)
    assert abs(e.value - 2.0) < 1e-6


def test_delay_tail_estimator_per_worker():
    est = DelayTailEstimator(m=3, buffer_size=16)
    # worker 2 is the straggler: 10x the delay of workers 0/1
    for _ in range(50):
        est.observe(0, 1.0)
        est.observe(1, 1.0)
        est.observe(2, 10.0)
    snap = est.snapshot()
    assert snap["workers"] == 3
    assert snap["count"] == [50, 50, 50]
    assert snap["p99"][2] == pytest.approx(10.0)
    assert snap["p99_max"] == pytest.approx(10.0)
    assert snap["ewma"][2] == pytest.approx(10.0)
    assert snap["ewma"][0] == pytest.approx(1.0)


def test_delay_tail_engine_wiring():
    """ClusterEngine(tail_estimator=...) feeds every sampled schedule and
    async trace into the estimator in-stream."""
    from repro.runtime import ClusterEngine, FastestK, make_delay_model
    est = DelayTailEstimator(m=6)
    eng = ClusterEngine(make_delay_model("bimodal"), 6, tail_estimator=est)
    eng.sample_schedule(10, FastestK(4))
    assert all(c == 10 for c in est.snapshot()["count"])
    eng.sample_async(20, 3)
    assert sum(est.snapshot()["count"]) == 60 + 20


def test_metrics_csv_carries_delay_tail(tmp_path):
    """Acceptance criterion: delay_tail_p99 metrics appear in
    write_metrics_csv output for traced runs."""
    from repro.experiments.run import main as exp_main
    out = tmp_path / "out"
    met = tmp_path / "met.csv"
    exp_main(["--strategies", "coded-gd", "--delays", "bimodal",
              "--steps", "8", "--n", "32", "--p", "8", "--m", "4",
              "--metrics-out", str(met), "--out", str(out),
              "--formats", "json"])
    with open(met) as f:
        rows = list(csv.DictReader(f))
    assert rows and float(rows[0]["delay_tail_p99_max"]) > 0
    assert int(rows[0]["delay_tail_p99_workers"]) == 4


# ---------------------------------------------------------------------------
# run store
# ---------------------------------------------------------------------------


def _tiny_result(seed=0):
    from repro.experiments import (DelayAxis, ExperimentSpec, PlacementAxis,
                                   ProblemAxis, StrategyAxis, TrialsAxis,
                                   execute, plan)
    spec = ExperimentSpec(
        problems=(ProblemAxis.synthetic(32, 8),),
        strategies=(StrategyAxis("uncoded"),),
        delays=DelayAxis.of("bimodal", m=4),
        trials=TrialsAxis(trials=1, seed=seed),
        placement=PlacementAxis(mode="single"), steps=6)
    return spec, execute(plan(spec), record_to=False)


def test_manifest_roundtrip(tmp_path):
    spec, result = _tiny_result()
    store = RunStore(str(tmp_path / "store"))
    run_id = record_experiment(result, store=store,
                               artifacts={"records_json": "a.json"})
    m = store.load(run_id)
    assert m["run_id"] == run_id
    assert m["kind"] == "experiment"
    assert m["spec_hash"] == spec_hash(spec)
    assert m["git_sha"] and m["timestamp"] and m["backend"]
    assert m["artifacts"] == {"records_json": "a.json"}
    [cell] = m["cells"]
    assert cell["strategy"] == "uncoded" and cell["delay"] == "bimodal"
    assert cell["wallclock_s"] > 0
    # index + query API agree with the manifest
    assert [r["run_id"] for r in store.runs()] == [run_id]
    assert store.latest()["run_id"] == run_id
    assert store.latest(spec_hash=spec_hash(spec))["run_id"] == run_id
    assert store.latest(spec_hash="nope") is None
    assert store.resolve(run_id[:10])["run_id"] == run_id  # unique prefix


def test_spec_hash_stability():
    spec_a, _ = _tiny_result(seed=0)
    spec_b, _ = _tiny_result(seed=0)
    assert spec_hash(spec_a) == spec_hash(spec_b)
    spec_c, _ = _tiny_result(seed=1)
    assert spec_hash(spec_a) != spec_hash(spec_c)


def test_execute_records_by_default(tmp_path, monkeypatch):
    """execute() writes a manifest into the env-configured store; =0
    disables; record_to=False skips."""
    from repro.experiments import execute, plan
    root = tmp_path / "envstore"
    monkeypatch.setenv("REPRO_RUNSTORE", str(root))
    spec, _ = _tiny_result()
    result = execute(plan(spec))
    assert result.run_id is not None
    assert RunStore(str(root)).load(result.run_id)["spec_hash"] == \
        spec_hash(spec)
    monkeypatch.setenv("REPRO_RUNSTORE", "0")
    assert execute(plan(spec)).run_id is None


def test_provenance_fields():
    p = provenance()
    assert set(p) >= {"git_sha", "timestamp", "backend", "jax_version",
                      "device_count"}
    assert p["timestamp"].endswith("+00:00") or "T" in p["timestamp"]


# ---------------------------------------------------------------------------
# diff CLI / regression gate
# ---------------------------------------------------------------------------


def _two_runs(tmp_path, slowdown=1.0):
    store = RunStore(str(tmp_path / "store"))
    _, result = _tiny_result()
    a = record_experiment(result, store=store)
    manifest = store.load(a)
    b = copy.deepcopy(manifest)
    b.pop("run_id")
    for cell in b["cells"]:
        cell["wallclock_s"] *= slowdown
    b_id = store.record(b)
    return store, a, b_id


def test_diff_identical_runs_exit_zero(tmp_path, capsys):
    store, a, b = _two_runs(tmp_path, slowdown=1.0)
    rc = diff_main([a, b, "--store", store.root])
    assert rc == 0
    out = capsys.readouterr().out
    assert "RESULT: OK" in out and "spec hash match" in out


def test_diff_flags_2x_slowdown(tmp_path, capsys):
    store, a, b = _two_runs(tmp_path, slowdown=2.0)
    rc = diff_main([a, b, "--store", store.root])
    assert rc == 1
    out = capsys.readouterr().out
    assert "regression" in out and "2.00x" in out
    # the reverse direction is an improvement, not a regression
    assert diff_main([b, a, "--store", store.root]) == 0
    # a looser gate lets 2x through
    assert diff_main([a, b, "--store", store.root,
                      "--threshold", "3.0"]) == 0


def test_diff_latest_refs_and_reports(tmp_path, monkeypatch, capsys):
    store, a, b = _two_runs(tmp_path, slowdown=2.0)
    monkeypatch.setenv("REPRO_RUNSTORE", store.root)
    js = tmp_path / "d.json"
    rc = diff_main(["latest~1", "latest", "--json", str(js)])
    assert rc == 1
    rep = json.loads(js.read_text())
    assert rep["exit_code"] == 1 and rep["regressions"] == 1


def test_diff_unknown_ref_exits_2(tmp_path, capsys):
    rc = diff_main(["nope-a", "nope-b", "--store", str(tmp_path / "s")])
    assert rc == 2


def test_diff_bench_baseline(tmp_path, capsys):
    base = {"bench": "x", "meta": {"git_sha": "a"},
            "results": [{"case": "r16", "us_per_call": 100.0,
                         "seconds_per_matrix": 1.0}]}
    cand = copy.deepcopy(base)
    cand["meta"]["git_sha"] = "b"              # meta never gates
    base_p = tmp_path / "base.json"
    cand_p = tmp_path / "cand.json"
    base_p.write_text(json.dumps(base))
    cand_p.write_text(json.dumps(cand))
    assert diff_main([str(cand_p), "--against-baseline",
                      str(base_p)]) == 0
    cand["results"][0]["us_per_call"] = 250.0
    cand_p.write_text(json.dumps(cand))
    rc = diff_main([str(cand_p), "--against-baseline", str(base_p)])
    assert rc == 1
    assert "us_per_call" in capsys.readouterr().out


def test_bench_meta_stamp():
    from benchmarks.common import bench_meta
    meta = bench_meta()
    assert set(meta) >= {"git_sha", "timestamp", "backend", "jax_version"}
