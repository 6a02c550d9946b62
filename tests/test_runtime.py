"""repro.runtime: scan runners vs legacy loops, engine accounting, async
staleness bound, strategy registry, fixed points, CLI harness."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (gd_step, hadamard_encoder, identity_encoder,
                        make_encoded_problem, make_lifted_problem,
                        original_objective, phi_quadratic,
                        replication_encoder, pad_rows, bimodal_delays,
                        constant_delays)
from repro.core.data_parallel import prox_step
from repro.runtime import (AdversarialRotation, ClusterEngine, Deadline,
                           FastestK, ProblemSpec, available_strategies,
                           get_strategy, make_delay_model, make_policy,
                           scan_async, scan_bcd, scan_gd, scan_prox)

M, K, P, N = 16, 12, 64, 256


@pytest.fixture(scope="module")
def spec():
    return ProblemSpec.synthetic(N, P, noise=0.5, lam=0.05, seed=0)


@pytest.fixture(scope="module")
def engine():
    return ClusterEngine(bimodal_delays(), M, seed=0)


@pytest.fixture(scope="module")
def schedule(engine):
    return engine.sample_schedule(60, FastestK(K))


def _problem(spec, enc):
    return make_encoded_problem(spec.X, spec.y, pad_rows(enc, M), M,
                                lam=spec.lam)


# ---------------------------------------------------------------------------
# scan-fused runners reproduce the legacy per-step loops
# ---------------------------------------------------------------------------

def test_scan_gd_matches_legacy_loop(spec, schedule):
    prob = _problem(spec, hadamard_encoder(N, 2.0))
    step = 0.01
    w_scan, tr_scan = scan_gd(prob, jnp.asarray(schedule.masks), step,
                              jnp.zeros(P), h="l2")
    w = jnp.zeros(P)
    tr = []
    for t in range(schedule.steps):
        w = gd_step(prob, w, jnp.asarray(schedule.masks[t]), step, h="l2")
        tr.append(float(original_objective(prob, w, h="l2")))
    # float32 scan trace vs the loop's Python-float trace of objectives
    # near 30: a rounding difference, so the tolerance is relative
    np.testing.assert_allclose(np.asarray(tr_scan), np.asarray(tr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w_scan), np.asarray(w), atol=1e-5)


def test_scan_prox_matches_legacy_loop(spec, schedule):
    prob = _problem(spec, hadamard_encoder(N, 2.0))
    step = 0.005
    w_scan, tr_scan = scan_prox(prob, jnp.asarray(schedule.masks), step,
                                jnp.zeros(P))
    w = jnp.zeros(P)
    tr = []
    for t in range(schedule.steps):
        w = prox_step(prob, w, jnp.asarray(schedule.masks[t]), step)
        tr.append(float(original_objective(prob, w, h="l1")))
    # float32 scan trace vs the loop's Python-float trace of objectives
    # near 30: a rounding difference, so the tolerance is relative
    np.testing.assert_allclose(np.asarray(tr_scan), np.asarray(tr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w_scan), np.asarray(w), atol=1e-5)


def test_scan_bcd_matches_legacy_loop(spec, schedule):
    enc = pad_rows(hadamard_encoder(P, 2.0), M)
    val, grad = phi_quadratic(spec.y)
    prob = make_lifted_problem(spec.X, enc, M, val, grad)
    step = 0.9 / (spec.lipschitz() * 2.0)
    v0 = jnp.zeros((M, prob.XS.shape[-1]))
    v_scan, tr_scan = scan_bcd(prob, jnp.asarray(schedule.masks), step, v0)

    import jax

    @jax.jit
    def legacy_step(v, mask):
        z = jnp.einsum("mnb,mb->mn", prob.XS, v).sum(axis=0)
        d = -step * jnp.einsum("mnb,n->mb", prob.XS, prob.phi_grad(z))
        return v + mask[:, None] * d, prob.phi_val(z)

    v = v0
    tr = []
    for t in range(schedule.steps):
        v, fval = legacy_step(v, jnp.asarray(schedule.masks[t]))
        tr.append(float(fval))
    tr.append(float(val(jnp.einsum("mnb,mb->n", prob.XS, v))))
    np.testing.assert_allclose(np.asarray(tr_scan), np.asarray(tr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_scan), np.asarray(v), atol=1e-5)


def test_run_encoded_wrappers_use_scan(spec, schedule):
    """The legacy core entry points now delegate; traces stay identical."""
    from repro.core import run_encoded_gd
    prob = _problem(spec, hadamard_encoder(N, 2.0))
    w1, tr1 = run_encoded_gd(prob, schedule.masks, 0.01)
    w2, tr2 = scan_gd(prob, jnp.asarray(schedule.masks), 0.01,
                      jnp.zeros(P), h="l2")
    np.testing.assert_allclose(tr1, np.asarray(tr2), atol=1e-6)


# ---------------------------------------------------------------------------
# engine: schedules, policies, wall-clock accounting
# ---------------------------------------------------------------------------

def test_schedule_wallclock_matches_order_statistic():
    """Barrier accounting == k-th order statistic of (delay + compute)."""
    eng = ClusterEngine(bimodal_delays(), M, seed=7)
    sched = eng.sample_schedule(20, FastestK(K))
    assert (np.diff(sched.times) > 0).all()
    for ev in sched.events:
        kth = np.sort(ev.arrivals - ev.start)[K - 1]
        assert ev.commit - ev.start == pytest.approx(
            kth + eng.master_overhead)
        assert ev.active.size == K


def test_adversarial_policy_sweeps_all_workers():
    eng = ClusterEngine(constant_delays(1.0), M, seed=0)
    sched = eng.sample_schedule(2 * M, AdversarialRotation(K))
    erased = (sched.masks == 0.0)
    assert erased.any(axis=0).all(), "every worker must be erased at least once"
    assert (sched.masks == 1.0).any(axis=0).all()
    assert (sched.masks.sum(axis=1) == K).all()


def test_deadline_policy_bounds_and_floor():
    eng = ClusterEngine(bimodal_delays(), M, seed=3)
    sched = eng.sample_schedule(30, Deadline(deadline=2.0, k_min=4))
    assert (sched.masks.sum(axis=1) >= 4).all()
    for ev in sched.events:
        # every worker beyond the floor made the deadline
        if ev.active.size > 4:
            assert ((ev.arrivals - ev.start)[ev.active]
                    <= 2.0 + eng.compute_time + 1e-12).all()


def test_adaptive_k_policy_overlap():
    eng = ClusterEngine(bimodal_delays(), M, seed=5)
    policy = make_policy("adaptive-k", beta=2.0, k_min=4)
    sched = eng.sample_schedule(30, policy)
    need = int(np.floor(M / 2.0)) + 1
    for a, b in zip(sched.events[:-1], sched.events[1:]):
        assert np.intersect1d(a.active, b.active).size >= need


# ---------------------------------------------------------------------------
# async: staleness bound + per-arrival accounting
# ---------------------------------------------------------------------------

def test_async_staleness_bound_respected():
    eng = ClusterEngine(bimodal_delays(), M, seed=1)
    for bound in (0, 3, 8):
        tr = eng.sample_async(300, staleness_bound=bound)
        assert tr.staleness.max() <= bound
        assert (tr.staleness >= 0).all()
        assert (np.diff(tr.times) >= 0).all()
        # read version + staleness reconstructs the master version sequence
        np.testing.assert_array_equal(tr.read_versions + tr.staleness,
                                      np.arange(300))


def test_async_strategy_converges(spec, engine):
    res = get_strategy("async").run(spec, engine, steps=40,
                                    staleness_bound=8)
    assert res.meta["max_staleness"] <= 8
    assert res.objective[-1] < 0.2 * res.objective[0]
    assert np.isfinite(res.objective).all()


def test_scan_async_zero_staleness_is_sequential_sgd(spec):
    """With staleness 0 every update reads the CURRENT iterate: the ring
    buffer must be exact — cross-check against a plain host loop."""
    prob = _problem(spec, identity_encoder(N))
    U = 64
    rng = np.random.default_rng(0)
    workers = rng.integers(0, M, size=U)
    step = 0.002
    w_dev, tr = scan_async(prob, jnp.asarray(workers),
                           jnp.zeros(U, jnp.int32), step,
                           jnp.zeros(P), buffer_size=1, h="l2")
    w = np.zeros(P)
    SX, Sy = np.asarray(prob.SX), np.asarray(prob.Sy)
    for i in workers:
        g = SX[i].T @ (SX[i] @ w - Sy[i]) * (M / (prob.n * prob.beta))
        w = w - step * (g + prob.lam * w)
    np.testing.assert_allclose(np.asarray(w_dev), w, atol=1e-5)


# ---------------------------------------------------------------------------
# strategies: registry + fixed points
# ---------------------------------------------------------------------------

def test_registry_has_all_paper_strategies():
    names = available_strategies()
    for want in ["coded-gd", "coded-prox", "coded-lbfgs", "coded-bcd",
                 "uncoded", "replication", "async"]:
        assert want in names
    with pytest.raises(KeyError):
        get_strategy("nope")


@pytest.mark.parametrize("name", ["uncoded", "replication"])
def test_full_participation_recovers_ridge_optimum(spec, name):
    """With no erasures (k = m) uncoded/replication gradients are EXACT, so
    the run converges to the known closed-form ridge fixed point."""
    eng = ClusterEngine(constant_delays(0.1), M, seed=0)
    res = get_strategy(name).run(spec, eng, steps=400, k=M)
    w_star = spec.w_star()
    prob = _problem(spec, identity_encoder(N))
    f_star = float(original_objective(prob, jnp.asarray(w_star), h="l2"))
    assert res.final_objective == pytest.approx(f_star, rel=1e-3)
    np.testing.assert_allclose(res.w, w_star, atol=1e-2)


def test_coded_gd_near_optimum_under_erasures(spec, engine):
    res = get_strategy("coded-gd").run(spec, engine, steps=300, k=K)
    w_star = spec.w_star()
    prob = _problem(spec, hadamard_encoder(N, 2.0))
    f_star = float(original_objective(prob, jnp.asarray(w_star), h="l2"))
    assert res.final_objective <= 1.1 * f_star


def test_strategies_share_delay_realization(spec, engine):
    """Same engine => same schedule => identical wall-clock for sync runs."""
    r1 = get_strategy("coded-gd").run(spec, engine, steps=25, k=K)
    r2 = get_strategy("uncoded").run(spec, engine, steps=25, k=K)
    np.testing.assert_array_equal(r1.times, r2.times)


# ---------------------------------------------------------------------------
# compare harness
# ---------------------------------------------------------------------------

def test_compare_cli_writes_traces(tmp_path):
    from repro.runtime.compare import main
    out = tmp_path / "cmp"
    records = main(["--strategies", "coded-gd,uncoded,async",
                    "--delays", "bimodal,exponential",
                    "--n", "128", "--p", "32", "--m", "8", "--k", "6",
                    "--steps", "20", "--out", str(out)])
    assert len(records) == 6
    import csv as _csv
    import json as _json
    data = _json.loads((out / "compare.json").read_text())
    assert {r["strategy"] for r in data} == {"coded-gd", "uncoded", "async"}
    for rec in data:
        assert len(rec["times"]) == len(rec["objective"]) > 0
        assert rec["wallclock_s"] > 0
    rows = list(_csv.reader((out / "compare.csv").open()))
    assert rows[0] == ["workload", "strategy", "delay", "trial", "step",
                       "time_s", "objective", "metric_name", "final_metric",
                       "skipped"]
    assert len(rows) - 1 == sum(len(r["times"]) for r in data)


def test_delay_model_registry():
    for name in ["bimodal", "power_law", "exponential", "multimodal",
                 "constant"]:
        model = make_delay_model(name)
        d = model(np.random.default_rng(0), 8)
        assert d.shape == (8,) and (d >= 0).all()
    with pytest.raises(KeyError):
        make_delay_model("gaussian")
