"""Property tests for the gradient-code families (hypothesis; alongside
tests/test_fault_properties.py).

Exactness (Tandon, arXiv 1612.03301): FRC decodes the full-batch gradient
for ANY mask with a survivor per cluster; cyclic repetition for ANY
<= beta-1 total erasures.  The stochastic code (Bitar et al., arXiv
1905.05383) trades exactness for an UNBIASED estimate with variance
bounded by the fixed-degree sampling formula."""
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core.gradient_coding import (make_cyclic, make_frc,  # noqa: E402
                                        make_stochastic)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 4), st.integers(2, 3), st.integers(0, 99),
       st.data())
def test_frc_exact_for_any_per_cluster_survivor_mask(clusters, beta, seed,
                                                     data):
    m = clusters * beta
    code = make_frc(m, beta)
    rng = np.random.default_rng(seed)
    g = rng.standard_normal(code.num_groups)      # one grad per data group
    # per cluster, keep a nonempty survivor subset (<= beta-1 erasures)
    mask = np.zeros(m)
    for c in range(code.num_clusters):
        members = np.flatnonzero(np.asarray(code.clusters) == c)
        keep = data.draw(st.integers(1, len(members)), label=f"keep{c}")
        mask[rng.permutation(members)[:keep]] = 1.0
    assert code.decode_exact_possible(mask)
    workers = g[np.asarray(code.clusters)]        # replica gradients
    a = np.asarray(code.decode_weights(mask))
    est = float(a @ workers) / code.num_groups
    np.testing.assert_allclose(est, g.mean(), rtol=1e-6, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 10), st.integers(2, 3), st.integers(0, 49),
       st.data())
def test_cyclic_exact_under_total_erasure_budget(m, beta, seed, data):
    code = make_cyclic(m, beta=beta, seed=seed)
    n_erase = data.draw(st.integers(0, beta - 1), label="n_erase")
    erased = data.draw(st.permutations(range(m)), label="erased")[:n_erase]
    mask = np.ones(m)
    mask[list(erased)] = 0.0
    assert code.decode_exact_possible(mask)
    a = np.asarray(code.decode_weights(mask))
    B = np.asarray(code.B)
    # B^T a = 1 <=> the combined worker gradients equal the full-batch sum
    resid = B.T @ a - np.ones(m)
    tol = 1e-6 * (1.0 + float(np.abs(B).max()) * float(np.abs(a).max()) * m)
    assert float(np.abs(resid).max()) <= tol


@settings(max_examples=5, deadline=None)
@given(st.integers(5, 8), st.integers(2, 3), st.integers(0, 9))
def test_stochastic_unbiased_with_bounded_variance(m, beta, seed):
    rng = np.random.default_rng(1000 + seed)
    g = rng.standard_normal(m)                    # scalar grad per group
    active = rng.choice(m, m - 2, replace=False)  # any fixed active set
    mask = np.zeros(m)
    mask[active] = 1.0
    base = make_stochastic(m, beta=beta, seed=seed)
    assert base.stochastic and not base.decode_exact_possible(np.ones(m))

    draws = 400
    ests = np.empty(draws)
    for t in range(draws):
        code = base.at_step(t)                    # fresh group assignment
        workers = g[np.asarray(code.groups)].sum(axis=1)
        c = np.asarray(code.decode_weights(mask))
        ests[t] = float(c @ workers) / code.num_groups

    # fixed-degree sampling without replacement: exact estimator variance
    n_act = int(mask.sum())
    var_exact = g.var() * (m - beta) / ((m - 1) * n_act * beta)
    se = np.sqrt(var_exact / draws)
    assert abs(ests.mean() - g.mean()) <= 5.0 * se + 1e-12
    assert ests.var() <= 1.6 * var_exact + 1e-12
