"""Fused masked-gradient path (DESIGN.md §12): kernel-vs-reference
equivalence, REPRO_FUSED scan routing, cell-batched matrix execution,
R==1 single-trial routing, combine layout, and the fastest-k sampler
fast path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (bimodal_delays, hadamard_encoder,
                        make_encoded_problem, pad_rows)
from repro.kernels.coded_reduce import coded_combine_call, combine_layout
from repro.kernels.fused_step import (fused_enabled, fused_masked_gradient,
                                      pick_fused_block_rows)
from repro.runtime import (ClusterEngine, FastestK, ProblemSpec,
                           batched_scan_gd, scan_gd)
from repro.runtime.engine import make_delay_model


def _reference(SX, Sy, w, mask, *, n, beta):
    k = jnp.maximum(mask.sum(), 1.0)
    c = mask * (SX.shape[0] / k) / (n * beta)
    u = jnp.einsum("mrp,p->mr", SX, w) - Sy
    return jnp.einsum("m,mrp,mr->p", c, SX, u).astype(w.dtype)


def _operands(m, r, p, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    SX = jnp.asarray(rng.standard_normal((m, r, p)), dtype)
    Sy = jnp.asarray(rng.standard_normal((m, r)), dtype)
    w = jnp.asarray(rng.standard_normal(p), dtype)
    mask = jnp.asarray(rng.random(m) < 0.7, jnp.float32)
    return SX, Sy, w, mask


# ---------------------------------------------------------------------------
# kernel vs dense reference (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [4, 32])
@pytest.mark.parametrize("p", [37, 63])          # odd p: no lane alignment
def test_fused_matches_reference_odd_p(m, p):
    SX, Sy, w, mask = _operands(m, 8, p)
    out = fused_masked_gradient(SX, Sy, w, mask, n=m * 8 // 2, beta=2.0,
                                interpret=True)
    ref = _reference(SX, Sy, w, mask, n=m * 8 // 2, beta=2.0)
    assert out.shape == (p,) and out.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_fused_bf16():
    SX, Sy, w, mask = _operands(8, 16, 64, dtype=jnp.bfloat16)
    out = fused_masked_gradient(SX, Sy, w, mask, n=64, beta=2.0,
                                interpret=True)
    ref = _reference(SX.astype(jnp.float32), Sy.astype(jnp.float32),
                     w.astype(jnp.float32), mask, n=64, beta=2.0)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=0.1, rtol=0.05)


def test_fused_block_rows_sweep():
    SX, Sy, w, mask = _operands(4, 12, 40)
    full = fused_masked_gradient(SX, Sy, w, mask, n=24, beta=2.0,
                                 interpret=True, block_rows=12)
    for br in (1, 2, 3, 4, 6):
        out = fused_masked_gradient(SX, Sy, w, mask, n=24, beta=2.0,
                                    interpret=True, block_rows=br)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   atol=1e-5)


def test_fused_all_masked_out_is_zero_safe():
    SX, Sy, w, _ = _operands(4, 8, 16)
    out = fused_masked_gradient(SX, Sy, w, jnp.zeros(4), n=16, beta=2.0,
                                interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_pick_fused_block_rows_divides_and_fits():
    for r, p in [(64, 64), (96, 512), (4096, 128)]:
        br = pick_fused_block_rows(r, p)
        assert r % br == 0
        assert 2 * br * p * 4 <= 8 * 2 ** 20


# ---------------------------------------------------------------------------
# scan-level routing: REPRO_FUSED=1 vs the dense path
# ---------------------------------------------------------------------------

def test_scan_gd_fused_matches_dense(monkeypatch):
    """The full scan under the fused kernel equals the dense-einsum scan.

    ``fused_enabled`` is a trace-time branch, so each flag flip needs a
    fresh trace — hence ``jax.clear_caches`` around each run."""
    spec = ProblemSpec.synthetic(128, 48, noise=0.5, lam=0.05, seed=3)
    prob = make_encoded_problem(spec.X, spec.y,
                                pad_rows(hadamard_encoder(128, 2.0), 8), 8,
                                lam=spec.lam)
    sched = ClusterEngine(bimodal_delays(), 8, seed=1).sample_schedule(
        15, FastestK(6))
    masks = jnp.asarray(sched.masks)
    w0 = jnp.zeros(48)

    monkeypatch.setenv("REPRO_FUSED", "0")
    jax.clear_caches()
    assert not fused_enabled()
    w_d, tr_d = scan_gd(prob, masks, 0.05, w0)

    monkeypatch.setenv("REPRO_FUSED", "1")
    jax.clear_caches()
    assert fused_enabled()
    w_f, tr_f = scan_gd(prob, masks, 0.05, w0)
    jax.clear_caches()                      # don't leak fused traces

    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_d), atol=1e-4)
    np.testing.assert_allclose(np.asarray(tr_f), np.asarray(tr_d), atol=1e-4)


# ---------------------------------------------------------------------------
# shared-slab body: one pass over SX for every realization of a vmap
# ---------------------------------------------------------------------------

def _kernels(fn, *args):
    """(body name, grid) of every pallas_call in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["jaxpr"].debug_info.func_name,
                              tuple(eqn.params["grid_mapping"].grid)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _batched_operands(R, m, r, p, seed=0):
    """Shared SX/Sy, R iterates and R masks: realization 0 has every
    worker erased (the k clamp), the others keep 1..m workers."""
    SX, Sy, _, _ = _operands(m, r, p, seed=seed)
    rng = np.random.default_rng(seed + 1)
    W = jnp.asarray(rng.standard_normal((R, p)), jnp.float32)
    masks = np.zeros((R, m), np.float32)
    for i in range(1, R):
        masks[i, rng.permutation(m)[:1 + (i * (m - 1)) // (R - 1)]] = 1.0
    return SX, Sy, W, jnp.asarray(masks)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


@pytest.mark.parametrize("R", [2, 16])
@pytest.mark.parametrize("m", [4, 32])
@pytest.mark.parametrize("p", [37, 63])
def test_shared_body_matches_per_realization_and_dense(R, m, p, monkeypatch):
    from repro.core.data_parallel import EncodedProblem, masked_gradient
    import repro.kernels.fused_step as fs
    r, n = 24, m * 12                      # three row blocks of 8
    SX, Sy, W, masks = _batched_operands(R, m, r, p)
    grad = jax.vmap(lambda w, mask: fused_masked_gradient(
        SX, Sy, w, mask, n=n, beta=2.0, interpret=True, block_rows=8))
    assert _kernels(grad, W, masks) == [("_shared_body", (1, m, 3))]
    shared = grad(W, masks)
    monkeypatch.setattr(fs, "fused_body", lambda *a, **kw: "fused")
    jax.clear_caches()                      # the rule's trace is cached
    assert _kernels(grad, W, masks) == [("_fused_body", (R, m, 3))]
    per = grad(W, masks)
    jax.clear_caches()
    prob = EncodedProblem(SX=SX, Sy=Sy, X=SX[0], y=Sy[0], lam=0.0,
                          beta=2.0, n=n)
    dense = jax.vmap(lambda w, mask: masked_gradient(prob, w, mask))(W, masks)
    assert shared.shape == (R, p) and shared.dtype == W.dtype
    assert np.all(np.asarray(shared[0]) == 0.0)        # all erased
    assert _rel(shared, per) <= 1e-5
    assert _rel(shared, dense) <= 1e-5


def test_batched_sx_keeps_per_realization_grid():
    """A batched SX (one problem per realization) is no shared slab: the
    rule keeps the per-realization body with the vmap as a grid axis."""
    R, m, r, p = 3, 4, 16, 40
    SX, Sy, W, masks = _batched_operands(R, m, r, p)
    SXb = jnp.stack([SX * (1.0 + i) for i in range(R)])
    grad = jax.vmap(lambda sx, w, mask: fused_masked_gradient(
        sx, Sy, w, mask, n=32, beta=2.0, interpret=True, block_rows=8))
    assert _kernels(grad, SXb, W, masks) == [("_fused_body", (R, m, 2))]
    out = grad(SXb, W, masks)
    for i in range(R):
        ref = _reference(SXb[i], Sy, W[i], masks[i], n=32, beta=2.0)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_unbatched_call_is_todays_body_bitwise():
    from repro.kernels.fused_step import _fused_call
    SX, Sy, w, mask = _operands(8, 16, 50)
    call = lambda SX, Sy, w, mask: fused_masked_gradient(
        SX, Sy, w, mask, n=64, beta=2.0, interpret=True, block_rows=8)
    assert _kernels(call, SX, Sy, w, mask) == [("_fused_body", (8, 2))]
    k = jnp.maximum(mask.sum(), 1.0)
    c = (mask * (8 / k) / (64 * 2.0)).astype(jnp.float32)
    direct = _fused_call(SX, Sy, w, c, interpret=True, block_rows=8)
    assert np.array_equal(np.asarray(call(SX, Sy, w, mask)),
                          np.asarray(direct))


@pytest.mark.parametrize("R,p,body", [(16, 6000, "fused-shared"),
                                      (64, 6000, "fused-shared"),
                                      (1, 6000, "fused"),
                                      (1031, 6000, "fused")])
def test_fused_body_choice(R, p, body):
    """One iterate, or a realization count with no block that fits VMEM
    (a large prime), takes the per-realization body."""
    from repro.kernels.fused_step import fused_body
    assert fused_body(R, p) == body
    assert fused_body(R, p, shared=False) == "fused"


def test_pick_fused_block_rows_budgets_shared_residents():
    """At the ridge cell's shape the slab, its f32 and bf16 copies and the
    (R, p) W and accumulator all fit the shared body's budget."""
    from repro.kernels.fused_step import _SHARED_VMEM_BUDGET
    r, p, R = 256, 6000, 16
    br = pick_fused_block_rows(r, p, 4, _SHARED_VMEM_BUDGET, realizations=R)
    assert r % br == 0 and br % 128 == 0
    assert 2 * br * p * 4 + br * p * 10 + 4 * R * p * 4 <= _SHARED_VMEM_BUDGET
    assert pick_fused_block_rows(r, p, 4, _SHARED_VMEM_BUDGET,
                                 realizations=64) < br


def _runner_problem():
    spec = ProblemSpec.synthetic(128, 40, noise=0.5, lam=0.05, seed=5)
    prob = make_encoded_problem(spec.X, spec.y,
                                pad_rows(hadamard_encoder(128, 2.0), 8), 8,
                                lam=spec.lam)
    eng = ClusterEngine(bimodal_delays(), 8, seed=2)
    masks = jnp.asarray(np.stack([
        eng.sample_schedule(12, FastestK(5), realization=i).masks
        for i in range(4)]))
    return prob, masks


@pytest.mark.parametrize("runner,degrade", [
    ("batched_scan_gd", None), ("batched_scan_prox", None),
    ("batched_scan_gd", ("hold", 5, 0.5))])
def test_batched_runners_shared_body_match_dense(runner, degrade,
                                                 monkeypatch):
    """R=4 realizations through the vmapped runners: the shared-slab body
    (REPRO_FUSED=1) against the dense einsum path.  Every fourth round
    loses two workers, which the hold-degrade step holds through."""
    from repro.runtime import runners
    prob, masks = _runner_problem()
    masks = masks.at[:, 2::4, :].multiply(
        jnp.asarray(np.arange(8) >= 2, jnp.float32))
    assert bool((masks.sum(-1) < 5).any())
    run = getattr(runners, runner)
    out = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("REPRO_FUSED", flag)
        jax.clear_caches()
        w, tr = run(prob, masks, 0.05, jnp.zeros((4, 40)), degrade=degrade)
        out[flag] = (np.asarray(w), np.asarray(tr))
    jax.clear_caches()
    assert _rel(out["1"][0], out["0"][0]) <= 1e-5
    assert _rel(out["1"][1], out["0"][1]) <= 1e-5


@pytest.mark.parametrize("flag,R,path", [("1", 16, "fused-shared"),
                                         ("1", 1, "fused"),
                                         ("0", 16, "dense")])
def test_runner_span_names_kernel_path(flag, R, path, monkeypatch):
    from repro.obs.trace import TraceRecorder
    from repro.runtime import runners
    prob, masks = _runner_problem()
    masks = jnp.tile(masks, (4, 1, 1))[:R]
    monkeypatch.setenv("REPRO_FUSED", flag)
    jax.clear_caches()
    rec = TraceRecorder()
    with rec.activate():
        runners.batched_scan_gd(prob, masks, 0.05, jnp.zeros((R, 40)))
    jax.clear_caches()
    (ev,) = rec.spans()
    assert ev.args["kernel"] == path


# ---------------------------------------------------------------------------
# R == 1 routes through the single-trial scan
# ---------------------------------------------------------------------------

def test_batched_r1_matches_single_bitwise():
    spec = ProblemSpec.synthetic(128, 32, noise=0.5, lam=0.05, seed=0)
    prob = make_encoded_problem(spec.X, spec.y,
                                pad_rows(hadamard_encoder(128, 2.0), 8), 8,
                                lam=spec.lam)
    sched = ClusterEngine(bimodal_delays(), 8, seed=0).sample_schedule(
        12, FastestK(6))
    masks = jnp.asarray(sched.masks)
    w_s, tr_s = scan_gd(prob, masks, 0.05, jnp.zeros(32))
    w_b, tr_b = batched_scan_gd(prob, masks[None], 0.05,
                                jnp.zeros((1, 32)))
    assert w_b.shape == (1, 32) and tr_b.shape == (1, 12)
    assert np.array_equal(np.asarray(w_b[0]), np.asarray(w_s))
    assert np.array_equal(np.asarray(tr_b[0]), np.asarray(tr_s))


# ---------------------------------------------------------------------------
# cell-batched matrix execution == per-cell execution
# ---------------------------------------------------------------------------

def _matrix_spec(cell_batch, trials=3):
    from repro.experiments import (DelayAxis, ExperimentSpec, PlacementAxis,
                                   ProblemAxis, StrategyAxis, TrialsAxis)
    return ExperimentSpec(
        problems=(ProblemAxis.synthetic(128, 32, lam=0.05, h="l2"),),
        strategies=(StrategyAxis("coded-gd"),
                    StrategyAxis("coded-gd",
                                 options=(("step_size", 0.02),)),
                    StrategyAxis("uncoded")),
        delays=DelayAxis.of("bimodal", "power_law", m=8),
        trials=TrialsAxis(trials=trials),
        placement=PlacementAxis(mode="vmap", cell_batch=cell_batch),
        steps=10)


def test_cellbatched_matrix_matches_percell():
    from repro.experiments import execute, plan
    per = execute(plan(_matrix_spec(False)))
    bat = execute(plan(_matrix_spec(True)))
    assert len(per.records) == len(bat.records) == 6
    batched_groups = 0
    for rp, rb in zip(per.records, bat.records):
        assert rp["strategy"] == rb["strategy"]
        assert rp["delay"] == rb["delay"]
        np.testing.assert_allclose(np.asarray(rb["objective"], float),
                                   np.asarray(rp["objective"], float),
                                   atol=1e-4)
        if rb["meta"].get("cell_batched", 0) > 1:
            batched_groups += 1
    # the 4 coded-gd cells and the 2 uncoded cells each share one program
    assert batched_groups == 6


def test_cellbatched_trials1_keeps_run_schema():
    from repro.experiments import execute, plan
    per = execute(plan(_matrix_spec(False, trials=1)))
    bat = execute(plan(_matrix_spec(True, trials=1)))
    for rp, rb in zip(per.records, bat.records):
        np.testing.assert_allclose(np.asarray(rb["objective"], float),
                                   np.asarray(rp["objective"], float),
                                   atol=1e-4)
        assert set(rp.keys()) <= set(rb.keys()) | {"meta"}


# ---------------------------------------------------------------------------
# combine layout: odd P without padding, 2-D weight acceptance
# ---------------------------------------------------------------------------

def test_combine_layout_divisor_over_pad():
    assert combine_layout(2048) == (2048, 0)
    assert combine_layout(37) == (37, 0)          # P <= block: one tile
    assert combine_layout(6144) == (2048, 0)
    assert combine_layout(97_536_768) == (1792, 0)   # 100m LM: 14 * 128
    # no multiple-of-128 divisor (the TPU lane rule): pad to whole blocks,
    # wasting under 128 columns per block
    for P_ in (2085, 2053, 6000):                 # 3*5*139, prime, ridge p
        bp, pad = combine_layout(P_)
        assert bp % 128 == 0 and (P_ + pad) % bp == 0
        assert pad < 128 * (P_ + pad) // bp


@pytest.mark.parametrize("P_", [37, 2085])
def test_combine_call_odd_p_and_2d_weights(P_):
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((6, P_)), jnp.float32)
    c = jnp.asarray(rng.standard_normal(6), jnp.float32)
    ref = jnp.einsum("m,mp->p", c, g)
    out1 = coded_combine_call(g, c, interpret=True)
    out2 = coded_combine_call(g, c[:, None], interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# fastest-k sampler fast path == reference loop, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["bimodal", "power_law", "constant"])
def test_sampler_fast_path_bit_identical(model):
    eng = ClusterEngine(make_delay_model(model), 16, seed=4)
    for r in (0, 2):
        fast = eng.sample_schedule(25, FastestK(12), realization=r)
        rng = np.random.default_rng(eng._trial_seed(r))
        slow = eng._sample_generic(rng, 25, FastestK(12))
        assert np.array_equal(fast.masks, slow.masks)
        assert np.array_equal(fast.times, slow.times)
        for ef, es in zip(fast.events, slow.events):
            assert ef.start == es.start and ef.commit == es.commit
            assert np.array_equal(ef.active, es.active)
            assert np.array_equal(ef.arrivals, es.arrivals)
