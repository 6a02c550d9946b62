"""Coded-SGD subsystem (DESIGN §15): exact decode through the real train
step, the engine bridge, the strategy/experiments lowering, chaos presets,
and the fault counters the tail estimator now carries."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gradient_coding import (make_code, make_cyclic,
                                        make_frc)
from repro.data.pipeline import GroupBatcher, TokenStream
from repro.models import transformer as T
from repro.obs.sketch import DelayTailEstimator
from repro.optim import adamw_init, cosine_schedule
from repro.runtime import (ClusterEngine, FastestK, get_strategy,
                           make_delay_model)
from repro.runtime.faults import FAULT_PRESETS, make_fault_model
from repro.train.coded import (CodedTrainer, TrainProblem,
                               TrainerConfig, build_coded_train_step,
                               run_coded_sgd)

M = 8


def _tiny_cfg():
    return TrainProblem(seq_len=16, vocab=64).build_cfg()


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# exact decode through the REAL train step (Tandon, arXiv 1612.03301)
# ---------------------------------------------------------------------------

def test_frc_step_exact_under_per_cluster_erasures():
    """FRC (beta=2): any erasure pattern leaving >=1 survivor per cluster
    yields the identical update — bit for bit across patterns (the
    surviving replica computed the same shard), and equal to the full-mask
    update within fp tolerance."""
    cfg = _tiny_cfg()
    code = make_frc(M, 2)
    batcher = GroupBatcher(TokenStream(cfg.vocab, seed=0), code, 1, 16,
                           seed=0)
    tokens, labels, coeff = batcher.next_batch()
    step = jax.jit(build_coded_train_step(
        cfg, cosine_schedule(1e-3, 2, 10), rows_per_group=1,
        num_groups=code.num_groups))
    params = T.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    args = (jnp.asarray(tokens), jnp.asarray(labels), jnp.asarray(coeff))

    # clusters are interleaved (worker i -> cluster i % 4), so each of
    # these loses one replica of EVERY cluster — the worst exact case
    mask_a = np.array([1, 1, 1, 1, 0, 0, 0, 0], np.float64)
    mask_b = np.array([0, 0, 0, 0, 1, 1, 1, 1], np.float64)
    outs = {}
    for name, mask in [("a", mask_a), ("b", mask_b),
                       ("full", np.ones(M))]:
        assert code.decode_exact_possible(mask)
        d = jnp.asarray(code.decode_weights(mask))
        p, _, met = step(params, opt, *args, d)
        outs[name] = (_leaves(p), float(met["loss"]))

    for la, lb in zip(outs["a"][0], outs["b"][0]):
        np.testing.assert_array_equal(la, lb)
    assert outs["a"][1] == pytest.approx(outs["b"][1], rel=0, abs=0)
    for la, lf in zip(outs["a"][0], outs["full"][0]):
        np.testing.assert_allclose(la, lf, rtol=2e-5, atol=1e-7)
    assert outs["a"][1] == pytest.approx(outs["full"][1], rel=1e-5)


def test_cyclic_decode_recovers_full_gradient():
    """Cyclic repetition: for any <= beta-1 TOTAL erasures the decode
    weights satisfy B^T a = 1, so the combined gradient equals the
    full-batch mean exactly."""
    code = make_cyclic(M, beta=3, seed=0)
    rng = np.random.default_rng(0)
    g = rng.standard_normal((M, 5))          # one gradient row per group
    workers = np.asarray(code.B) @ g         # what each worker computes
    for erased in [(), (2,), (6, 1)]:
        mask = np.ones(M)
        mask[list(erased)] = 0.0
        assert code.decode_exact_possible(mask)
        a = np.asarray(code.decode_weights(mask))
        assert np.all(a[list(erased)] == 0.0)
        est = (a @ workers) / code.num_groups
        np.testing.assert_allclose(est, g.mean(axis=0), rtol=1e-5,
                                   atol=1e-7)
    # beyond the threshold: no exactness claim, but finite weights
    mask = np.ones(M)
    mask[[0, 3, 5]] = 0.0
    assert not code.decode_exact_possible(mask)
    assert np.all(np.isfinite(code.decode_weights(mask)))


# ---------------------------------------------------------------------------
# engine bridge + strategy interface
# ---------------------------------------------------------------------------

def test_coded_trainer_runs_off_engine_schedule():
    cfg = _tiny_cfg()
    tcfg = TrainerConfig(m_workers=M, beta=2, wait_k=6, rows_per_worker=1,
                         seq_len=16, steps=3, lr=1e-3, warmup=1,
                         log_every=0)
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=1,
                        faults=make_fault_model("preset:ec2-tail"))
    tr = CodedTrainer(cfg, tcfg, eng, policy=FastestK(6))
    _, _, hist = tr.run()
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)
    times = [h["sim_time_s"] for h in hist]
    assert times == sorted(times)
    assert tr.last_schedule is not None
    # the loop consumed the engine's masks, not its own straggler model
    assert [h["active"] for h in hist] == \
        [int(m.sum()) for m in np.asarray(tr.last_schedule.masks) > 0]


def test_run_coded_sgd_strategy_surface():
    spec = TrainProblem(seq_len=16, vocab=64)
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=0)
    res = get_strategy("coded-sgd").run(spec, eng, steps=2, k=6,
                                        code="stochastic", warmup=1)
    assert res.strategy == "coded-sgd"
    assert len(res.objective) == 2 and np.all(np.isfinite(res.objective))
    assert res.meta["code"] == "stochastic"
    assert res.meta["exact_fraction"] == 0.0    # approximate code
    with pytest.raises(ValueError, match="unknown coded-sgd config"):
        run_coded_sgd(spec, eng, steps=2, nonsense=1)


def test_experiments_train_cell_plan_and_execute(tmp_path, monkeypatch):
    from repro.experiments.execute import execute
    from repro.experiments.plan import plan
    from repro.experiments.spec import (DelayAxis, ExperimentSpec, ObsAxis,
                                        PlacementAxis, ProblemAxis,
                                        StrategyAxis, TrialsAxis)
    monkeypatch.setenv("REPRO_RUNSTORE", str(tmp_path / "store"))
    spec = ExperimentSpec(
        problems=(ProblemAxis.train("deepseek-7b", seq_len=16, vocab=64),),
        strategies=(StrategyAxis(name="coded-sgd", k=6,
                                 options=(("code", "cyclic"),
                                          ("warmup", 1))),
                    StrategyAxis(name="uncoded", k=M),
                    StrategyAxis(name="coded-gd")),
        delays=DelayAxis(delays=("bimodal",), m=M),
        trials=TrialsAxis(trials=1, eval_every=1, seed=0),
        placement=PlacementAxis(mode="single"),
        steps=2, obs=ObsAxis())
    pl = plan(spec)
    assert len(pl.cells) == 3
    skips = {c.resolved_strategy: c.skip for c in pl.cells}
    assert skips["coded-sgd"] is None and skips["uncoded"] is None
    assert "train-kind" in skips["coded-gd"]
    result = execute(pl)
    recs = {r["strategy"]: r for r in result.records}
    assert "skipped" in recs["coded-gd"]
    for name, code in [("coded-sgd", "cyclic"), ("uncoded", "uncoded")]:
        rec = recs[name]
        assert rec["metric_name"] == "loss"
        assert np.isfinite(rec["final_metric"])
        assert rec["meta"]["code"] == code
    assert result.run_id is not None
    assert (tmp_path / "store" / result.run_id / "manifest.json").exists()


# ---------------------------------------------------------------------------
# chaos presets + fault counters
# ---------------------------------------------------------------------------

def test_fault_presets_parse_and_compose():
    for name in FAULT_PRESETS:
        fm = make_fault_model(f"preset:{name}")
        assert fm is not None and len(fm.injectors) >= 1
    composed = make_fault_model("preset:ec2-tail;crash:p=0.5,at=0.1")
    base = make_fault_model("preset:ec2-tail")
    assert len(composed.injectors) == len(base.injectors) + 1
    assert composed.spec == "preset:ec2-tail;crash:p=0.5,at=0.1"
    with pytest.raises(KeyError, match="ec2-tail"):
        make_fault_model("preset:no-such-preset")


def test_delay_tail_estimator_counts_faults():
    est = DelayTailEstimator(M)
    eng = ClusterEngine(make_delay_model("bimodal"), M, seed=3,
                        faults=make_fault_model("preset:zone-outage"),
                        tail_estimator=est)
    eng.sample_schedule(12, FastestK(6))
    snap = est.snapshot()
    assert snap["faults"]["schedules"] == 1
    assert snap["faults"]["crashes"] + snap["faults"]["blackouts"] > 0
    # clean engines keep the historical snapshot key set
    clean = DelayTailEstimator(M)
    ClusterEngine(make_delay_model("bimodal"), M, seed=3,
                  tail_estimator=clean).sample_schedule(12, FastestK(6))
    assert "faults" not in clean.snapshot()


def test_make_code_registry():
    assert make_code("uncoded", M).num_groups == M
    assert make_code("bernoulli", M, beta=2).stochastic
    with pytest.raises(KeyError, match="frc"):
        make_code("no-such-code", M)
