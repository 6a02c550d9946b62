"""repro.experiments (DESIGN.md §10): spec validation, plan compilation
(skip materialization, up-front misconfig errors), execute equivalence with
the legacy harnesses, the placement axis (single / vmap / sharded incl. a
real multi-device mesh), eval_every=0, TrialsResult round-trips and the
unified CLI."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.experiments import (DelayAxis, ExperimentSpec, PlacementAxis,
                               ProblemAxis, StrategyAxis, TrialsAxis,
                               execute, plan, run)
from repro.runtime import ClusterEngine, ProblemSpec, get_strategy, \
    make_delay_model
from repro.runtime.strategies import check_trials, resolve_eval_every

N, P, M, K, T, R = 128, 32, 8, 6, 20, 3


def _synth_spec(strategies=("coded-gd", "uncoded"), delays=("bimodal",),
                trials=1, eval_every=1, placement="vmap", steps=T, **st_kw):
    return ExperimentSpec(
        problems=(ProblemAxis.synthetic(N, P),),
        strategies=tuple(StrategyAxis(s, **st_kw) for s in strategies),
        delays=DelayAxis(delays=tuple(delays), m=M),
        trials=TrialsAxis(trials=trials, eval_every=eval_every),
        placement=PlacementAxis(mode=placement), steps=steps)


# ---------------------------------------------------------------------------
# spec + plan
# ---------------------------------------------------------------------------

def test_plan_resolves_synthetic_defaults():
    pl = plan(_synth_spec(delays=("bimodal", "exponential")))
    assert len(pl.cells) == 4                     # 2 delays x 2 strategies
    c = pl.cells[0]
    assert (c.m, c.k, c.steps) == (M, max(1, 3 * M // 4), T)
    assert c.skip is None and c.placement == "vmap"
    # delays outer, strategies inner — the legacy compare order
    assert [(c.delay, c.resolved_strategy) for c in pl.cells] == [
        ("bimodal", "coded-gd"), ("bimodal", "uncoded"),
        ("exponential", "coded-gd"), ("exponential", "uncoded")]


def test_plan_materializes_workload_skips_up_front():
    spec = ExperimentSpec(
        problems=(ProblemAxis.from_workload("ridge", "smoke"),),
        strategies=(StrategyAxis("coded"), StrategyAxis("coded-prox"),
                    StrategyAxis("nosuch")),
        delays=DelayAxis(), steps=8)
    pl = plan(spec)
    assert len(pl.cells) == 3
    assert pl.cells[0].skip is None
    assert pl.cells[0].resolved_strategy == "coded-lbfgs"  # alias resolved
    assert "l1" in pl.cells[1].skip                        # unsupported
    assert "unknown strategy" in pl.cells[2].skip
    assert pl.cells[1].metric_name == "subopt_gap"
    assert len(pl.skipped) == 2
    assert "SKIP" in pl.describe()


def test_plan_rejects_bad_eval_every_up_front():
    with pytest.raises(ValueError, match=r"steps % eval_every == 3"):
        plan(_synth_spec(trials=2, eval_every=7, steps=24))


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="at least one problem"):
        ExperimentSpec(problems=(), strategies=(StrategyAxis("x"),)
                       ).validate()
    with pytest.raises(ValueError, match="workload"):
        _synth_spec(delays=()).validate()         # synthetic needs delays
    with pytest.raises(ValueError, match="placement"):
        _synth_spec(placement="tpu-pod").validate()
    with pytest.raises(KeyError, match="nosuch"):
        plan(_synth_spec(strategies=("nosuch",)))  # synthetic: fail fast


# ---------------------------------------------------------------------------
# execute == the legacy harnesses
# ---------------------------------------------------------------------------

def test_execute_matches_legacy_run_matrix():
    from repro.runtime.compare import run_matrix
    legacy = run_matrix(["coded-gd", "async"], ["bimodal"], n=N, p=P, m=M,
                        steps=T)
    spec = ExperimentSpec(
        problems=(ProblemAxis.synthetic(N, P),),
        strategies=(StrategyAxis("coded-gd", encoder="hadamard",
                                 policy="fastest-k"),
                    StrategyAxis("async", encoder="hadamard",
                                 policy="fastest-k")),
        delays=DelayAxis(delays=("bimodal",), m=M), steps=T)
    assert execute(plan(spec)).records == legacy


def test_execute_matches_legacy_workload_matrix():
    from repro.workloads.runner import run_workload_matrix
    legacy = run_workload_matrix(["ridge"], ["coded", "coded-bcd"],
                                 preset="smoke", steps=8)
    spec = ExperimentSpec(
        problems=(ProblemAxis.from_workload("ridge", "smoke"),),
        strategies=(StrategyAxis("coded"), StrategyAxis("coded-bcd")),
        delays=DelayAxis(), steps=8)
    records = execute(plan(spec)).records
    assert records == legacy
    assert "skipped" in records[1]                # bcd can't score ridge


def test_outcomes_carry_raw_results():
    result = run(_synth_spec(strategies=("coded-gd",)))
    out = result.outcomes[0]
    assert out.result is not None and not out.skipped
    assert out.result.w.shape == (P,)
    assert out.record["final_objective"] == out.result.final_objective


# ---------------------------------------------------------------------------
# placement axis
# ---------------------------------------------------------------------------

def test_placement_single_matches_vmap():
    recs = {p: run(_synth_spec(strategies=("coded-gd", "async"), trials=R,
                               placement=p)).records
            for p in ("single", "vmap")}
    for rv, rs in zip(recs["vmap"], recs["single"]):
        np.testing.assert_allclose(rs["objective"], rv["objective"],
                                   atol=1e-5)
        np.testing.assert_allclose(rs["times"], rv["times"], atol=1e-9)
        assert rs["meta"]["batched"] is False
        assert rv["meta"]["batched"] is True


def test_placement_sharded_single_device_falls_back_to_vmap():
    rv = run(_synth_spec(strategies=("coded-gd",), trials=R)).records[0]
    rs = run(_synth_spec(strategies=("coded-gd",), trials=R,
                         placement="sharded")).records[0]
    np.testing.assert_array_equal(rs["objective"], rv["objective"])
    assert rs["meta"]["placement"] == "sharded"
    assert rs["meta"]["placement_devices"] >= 1


def test_placement_sharded_bcd_falls_back_with_note():
    rec = run(_synth_spec(strategies=("coded-bcd",), trials=R,
                          placement="sharded")).records[0]
    assert "placement_fallback" in rec["meta"]


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import numpy as np, jax
    assert len(jax.devices()) == 4, jax.devices()
    from repro.experiments import (DelayAxis, ExperimentSpec, PlacementAxis,
                                   ProblemAxis, StrategyAxis, TrialsAxis,
                                   run)
    def rec(placement):
        return run(ExperimentSpec(
            problems=(ProblemAxis.synthetic(128, 32),),
            strategies=(StrategyAxis("coded-gd"),),
            delays=DelayAxis(delays=("bimodal",), m=8),
            trials=TrialsAxis(trials=8),
            placement=PlacementAxis(mode=placement), steps=12)).records[0]
    v, s = rec("vmap"), rec("sharded")
    assert s["meta"]["placement_devices"] == 4, s["meta"]
    err = np.abs(np.asarray(v["objective"]) -
                 np.asarray(s["objective"])).max()
    assert err < 1e-5, err
    print("SHARDED_OK", err)
""")


def test_placement_sharded_multidevice_matches_vmap():
    """R=8 realizations via shard_map on a forced 4-device CPU mesh match
    the vmap placement to 1e-5 (the ROADMAP multi-device-trials item)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


# ---------------------------------------------------------------------------
# check_trials / eval_every=0
# ---------------------------------------------------------------------------

def test_check_trials_reports_remainder():
    with pytest.raises(ValueError) as e:
        check_trials(24, 2, 7)
    assert "steps % eval_every == 3" in str(e.value)
    with pytest.raises(ValueError, match=">= 0"):
        check_trials(24, 2, -1)


def test_eval_every_zero_means_final_only():
    check_trials(24, 2, 0)                        # accepted
    assert resolve_eval_every(24, 0) == 24
    assert resolve_eval_every(24, 4) == 4
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=0)
    spec = ProblemSpec.synthetic(N, P, seed=0)
    res0 = get_strategy("coded-gd").run_batched(spec, eng, steps=T, trials=R,
                                                eval_every=0, k=K)
    dense = get_strategy("coded-gd").run_batched(spec, eng, steps=T,
                                                 trials=R, eval_every=1, k=K)
    assert res0.objective.shape == (R, 1)
    assert res0.times.shape == (R, 1)
    np.testing.assert_allclose(res0.objective[:, -1], dense.objective[:, -1],
                               atol=1e-6)
    np.testing.assert_array_equal(res0.times[:, -1], dense.times[:, -1])


# ---------------------------------------------------------------------------
# TrialsResult.realization / to_record round-trips
# ---------------------------------------------------------------------------

def test_trialsresult_realization_matches_single_run():
    """Realization r of a batched run == the single-trial run on the same
    child seed (engine.trial(r)), trace, wall-clock and iterate."""
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=0)
    spec = ProblemSpec.synthetic(N, P, seed=0)
    batched = get_strategy("coded-gd").run_batched(spec, eng, steps=T,
                                                   trials=R, k=K)
    for r in range(R):
        single = get_strategy("coded-gd").run(spec, eng.trial(r), steps=T,
                                              k=K)
        real = batched.realization(r)
        np.testing.assert_array_equal(real.times, single.times)
        np.testing.assert_allclose(real.objective, single.objective,
                                   atol=1e-5)
        np.testing.assert_allclose(real.w, single.w, atol=1e-5)
        assert real.schedule is not None
        np.testing.assert_array_equal(real.schedule.masks,
                                      single.schedule.masks)


def test_trialsresult_to_record_roundtrip():
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=0)
    spec = ProblemSpec.synthetic(N, P, seed=0)
    batched = get_strategy("coded-gd").run_batched(spec, eng, steps=T,
                                                   trials=R, k=K)
    rec = json.loads(json.dumps(batched.to_record()))
    assert rec["trials"] == R
    np.testing.assert_allclose(rec["times"], np.asarray(batched.times))
    np.testing.assert_allclose(rec["objective"],
                               np.asarray(batched.objective), rtol=1e-7)
    assert rec["final_objective"] == pytest.approx(
        float(batched.final_objective.mean()))
    assert rec["summary"]["wallclock_s"]["p95"] >= \
        rec["summary"]["wallclock_s"]["p50"]
    # realization(r).to_record() is a plain single-trial record
    rec_r = batched.realization(1).to_record()
    np.testing.assert_allclose(rec_r["objective"], rec["objective"][1],
                               rtol=1e-7)


def test_workload_run_trials_realization_matches_single_incl_extras():
    """Workload trials: realization r (sequential fallback, mf) matches the
    single run on engine.trial(r) — including the extras payload."""
    from repro.workloads import get_workload
    wl = get_workload("mf")
    ps = wl.preset("smoke")
    data = wl.build(ps)
    eng = wl.default_engine(ps)
    results = wl.run_trials("coded", eng, preset=ps, data=data, trials=2,
                            steps=3)
    single = wl.run("coded", eng.trial(1), preset=ps, data=data, steps=3)
    np.testing.assert_allclose(results[1].metric, single.metric, atol=1e-6)
    np.testing.assert_allclose(results[1].times, single.times, atol=1e-9)
    assert results[1].extras == single.extras
    assert results[1].extras["half_steps"]       # non-trivial payload


# ---------------------------------------------------------------------------
# unified CLI
# ---------------------------------------------------------------------------

def test_experiments_cli_end_to_end(tmp_path):
    from repro.experiments.run import main
    out = tmp_path / "exp"
    result = main(["--strategies", "coded-gd,uncoded", "--delays", "bimodal",
                   "--n", str(N), "--p", str(P), "--m", str(M),
                   "--steps", "12", "--trials", "2", "--eval-every", "4",
                   "--out", str(out)])
    assert len(result.records) == 2
    data = json.loads((out / "experiments.json").read_text())
    assert data == result.records
    assert (out / "experiments.csv").exists()
    assert (out / "summary.csv").exists()
    for rec in data:
        assert rec["trials"] == 2
        assert len(rec["objective"][0]) == 3      # 12 steps / eval_every 4


def test_workload_cells_honor_strategy_axis_config():
    """StrategyAxis config set by the user must reach workload cells too:
    async staleness/updates and an explicit policy are forwarded, not
    silently dropped."""
    spec = ExperimentSpec(
        problems=(ProblemAxis.from_workload("ridge", "smoke"),),
        strategies=(StrategyAxis("async", staleness_bound=4,
                                 async_updates=64),
                    StrategyAxis("coded-gd", policy="adversarial", k=5)),
        delays=DelayAxis(), steps=8)
    recs = execute(plan(spec)).records
    assert recs[0]["meta"]["staleness_bound"] == 4
    assert recs[0]["meta"]["updates"] == 64
    assert recs[1]["meta"]["policy"] == "AdversarialRotation"


def test_cli_explicit_delays_win_over_workload_native():
    from repro.experiments.run import main
    result = main(["--workloads", "ridge", "--strategies", "coded",
                   "--delays", "bimodal,power_law,exponential",
                   "--plan-only"])
    assert [c.delay for c in result.plan.cells] == [
        "bimodal", "power_law", "exponential"]


def test_experiments_cli_plan_only(capsys):
    from repro.experiments.run import main
    result = main(["--workloads", "ridge", "--strategies", "coded,nosuch",
                   "--plan-only"])
    assert result.outcomes == []
    captured = capsys.readouterr().out
    assert "ExperimentPlan" in captured and "SKIP" in captured


# ---------------------------------------------------------------------------
# failures fail: only UnsupportedStrategy becomes a skip record
# ---------------------------------------------------------------------------

class _LoweringFails:
    """Stands in for a kernel the chip's compiler refuses: every entry point
    raises a plain ValueError, as a Pallas block-shape error does."""

    name = "coded-gd"

    def run(self, *args, **kwargs):
        raise ValueError("block shape not divisible by (8, 128)")

    run_batched = run_cellbatched = run


@pytest.mark.parametrize("extra", [
    [],
    ["--trials", "4"],
    ["--delays", "bimodal,exponential", "--cell-batch"],
    ["--workloads", "ridge", "--steps", "4"],
], ids=["single", "trials", "cell-batch", "workload"])
def test_cell_error_propagates(monkeypatch, tmp_path, extra):
    from repro.experiments.run import main
    from repro.runtime import strategies
    monkeypatch.setitem(strategies._REGISTRY, "coded-gd", _LoweringFails)
    with pytest.raises(ValueError, match=r"\(8, 128\)"):
        main(["--strategies", "coded-gd", "--n", str(N), "--p", str(P),
              "--m", str(M), "--steps", "4", "--delays", "bimodal",
              "--out", str(tmp_path), *extra])


def test_cli_exits_nonzero_on_cell_error(tmp_path):
    """``python -m repro.experiments.run`` exits non-zero, and writes no
    records, when a cell raises anything but UnsupportedStrategy."""
    script = textwrap.dedent(f"""
        import runpy, sys
        from repro.runtime import strategies

        class Fails(strategies.Strategy):
            def run(self, *args, **kwargs):
                raise ValueError("lowering failed")
            run_batched = run

        strategies._REGISTRY["coded-gd"] = Fails
        sys.argv = ["run", "--strategies", "coded-gd", "--n", "64",
                    "--p", "16", "--m", "4", "--steps", "4",
                    "--out", {str(tmp_path / "out")!r}]
        runpy.run_module("repro.experiments.run", run_name="__main__")
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "lowering failed" in proc.stderr
    assert not (tmp_path / "out" / "experiments.json").exists()


@pytest.mark.parametrize("env_dir", [None, "placed-from-outside"])
def test_compile_cache_placement(tmp_path, env_dir):
    """The entry points' compile cache: where JAX_COMPILATION_CACHE_DIR
    says, else the fixed ``.jax_cache/`` at the repository root."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    script = ("import jax; from repro.compile_cache import "
              "enable_compile_cache; d = enable_compile_cache(); "
              "print(d); print(jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = (str(tmp_path / env_dir) if env_dir is not None
            else os.path.join(root, ".jax_cache"))
    assert returned == configured == want
