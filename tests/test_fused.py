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
