"""FRC gradient coding properties (hypothesis): exact decode under any mask
with surviving clusters; graceful degradation otherwise."""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import (make_frc, coded_weights, decode_exact_possible,
                        assignment_matrix)


@settings(max_examples=40, deadline=None)
@given(m_half=st.integers(2, 8), seed=st.integers(0, 999),
       drop=st.integers(0, 6))
def test_exact_decode_when_clusters_survive(m_half, seed, drop):
    m = 2 * m_half
    code = make_frc(m, 2)
    rng = np.random.default_rng(seed)
    mask = np.ones(m)
    mask[rng.choice(m, size=min(drop, m - 1), replace=False)] = 0.0
    c = np.asarray(coded_weights(code, jnp.asarray(mask)))
    G = assignment_matrix(code)
    per_cluster = c @ G
    if decode_exact_possible(code, mask):
        # every cluster's gradient enters with total weight exactly 1
        np.testing.assert_allclose(per_cluster, 1.0, atol=1e-6)
    else:
        # surviving clusters are rescaled uniformly; erased ones are 0
        alive = per_cluster > 0
        if alive.any():
            np.testing.assert_allclose(
                per_cluster[alive], per_cluster[alive][0], atol=1e-6)
        assert np.all(per_cluster[~alive] == 0.0)


def test_coded_gradient_equals_full_batch():
    """End-to-end: masked weighted gradient == full-batch gradient on a
    linear model when every cluster survives (the paper's erasure recovery
    for the general-loss extension, DESIGN §4)."""
    m, b = 8, 4
    code = make_frc(m, 2)
    rng = np.random.default_rng(0)
    # cluster data
    Xc = rng.standard_normal((b, 5, 3))   # 4 clusters x 5 samples x 3 feat
    yc = rng.standard_normal((b, 5))
    w = jnp.asarray(rng.standard_normal(3))

    def cluster_grad(j):
        X, y = jnp.asarray(Xc[j]), jnp.asarray(yc[j])
        return X.T @ (X @ w - y) / X.shape[0]

    full = sum(cluster_grad(j) for j in range(b)) / b
    mask = np.ones(m)
    mask[[0, 5]] = 0.0   # drops one replica of clusters 0 and 1
    assert decode_exact_possible(code, mask)
    c = np.asarray(coded_weights(code, jnp.asarray(mask)))
    agg = sum(c[i] * cluster_grad(code.clusters[i]) for i in range(m)) / b
    np.testing.assert_allclose(np.asarray(agg), np.asarray(full), rtol=1e-5)


def test_adversarial_tolerance_bound():
    """FRC with beta=2 tolerates ANY single-worker erasure pattern that
    leaves one replica per cluster — and the interleaved layout survives
    a contiguous block failure of m/2 - 1 neighbours."""
    m = 16
    code = make_frc(m, 2)
    for start in range(m):
        mask = np.ones(m)
        idx = (start + np.arange(m // 2 - 1)) % m
        mask[idx] = 0.0
        assert decode_exact_possible(code, mask)
