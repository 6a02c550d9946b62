"""Property tests (hypothesis) for erasure-obliviousness of the FRC code
(DESIGN.md §4, §14): as long as every data cluster keeps >= 1 live replica
the decoded gradient — and hence the whole optimization trajectory — does
not depend on WHICH replicas were erased; below that threshold degradation
is graceful (an unbiased mean over surviving clusters, never corruption).

Skipped when ``hypothesis`` is unavailable (it is not shipped in the
accelerator image; CI installs it from requirements.txt)."""
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core.gradient_coding import (coded_weights, decode_exact_possible,
                                        make_frc)

P = 6          # parameter dim of the toy linear problem


def _cluster_masks(beta: int, clusters: int, *, allow_empty: bool):
    """Per-cluster replica-survival bitmask: 1..2^beta-1 keeps >= 1 replica
    alive; 0 (only with ``allow_empty``) erases the whole cluster."""
    lo = 0 if allow_empty else 1
    return st.lists(st.integers(lo, 2 ** beta - 1),
                    min_size=clusters, max_size=clusters)


def _expand(code, bits):
    """Cluster bitmasks -> (m,) worker 0/1 mask (replica j of cluster c is
    alive iff bit j of ``bits[c]`` is set)."""
    mask = np.zeros(code.m)
    seen = [0] * code.num_clusters
    for i in range(code.m):
        c = int(code.clusters[i])
        if (bits[c] >> seen[c]) & 1:
            mask[i] = 1.0
        seen[c] += 1
    return mask


def _decode(code, cluster_grads, mask):
    """Combine per-worker replica gradients with the code's decode weights,
    reducing WITHIN each cluster first (the grouped tree-reduce shape of the
    masked psum): replicas of a cluster hold bit-identical values, so a
    cluster with survivors contributes its gradient exactly."""
    c = np.asarray(coded_weights(code, mask), np.float64)
    out = np.zeros(cluster_grads.shape[1])
    for cl in range(code.num_clusters):
        members = np.nonzero(code.clusters == cl)[0]
        out += c[members].sum() * cluster_grads[cl]
    return out / code.num_clusters


def _problem(seed):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(4, P, P)), rng.normal(size=(4, P))  # (A_c, b_c)


@settings(max_examples=30, deadline=None)
@given(bits=_cluster_masks(2, 4, allow_empty=False),
       seed=st.integers(0, 2 ** 16))
def test_decode_exact_whenever_every_cluster_survives(bits, seed):
    code = make_frc(8, beta=2)
    mask = _expand(code, bits)
    assert decode_exact_possible(code, mask)
    grads = np.random.default_rng(seed).normal(size=(4, P))
    np.testing.assert_allclose(_decode(code, grads, mask), grads.mean(0),
                               rtol=1e-6, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(bits_a=st.lists(st.integers(1, 3), min_size=40, max_size=40),
       bits_b=st.lists(st.integers(1, 3), min_size=40, max_size=40),
       seed=st.integers(0, 2 ** 16))
def test_trajectory_oblivious_to_which_replica_erased(bits_a, bits_b, seed):
    """Two runs that erase DIFFERENT replicas every step (but always keep a
    survivor per cluster) produce bit-identical iterates: the erasure
    pattern is unobservable above the decode threshold."""
    code = make_frc(8, beta=2)
    A, b = _problem(seed)

    def run(step_bits):
        w = np.zeros(P)
        for t in range(10):
            grads = A @ w - b                        # (clusters, P)
            bits = step_bits[4 * t:4 * t + 4]
            g = _decode(code, grads, _expand(code, bits))
            w = w - 0.05 * g
        return w

    wa, wb = run(bits_a), run(bits_b)
    assert np.array_equal(wa, wb)                    # not merely close
    assert np.isfinite(wa).all()


@settings(max_examples=30, deadline=None)
@given(bits=_cluster_masks(2, 4, allow_empty=True),
       seed=st.integers(0, 2 ** 16))
def test_degradation_below_threshold_is_graceful(bits, seed):
    """With whole clusters erased the decode is still an unbiased mean over
    the SURVIVING clusters (rescaled, finite, never NaN) — and erasing more
    workers can only shrink the surviving-cluster set."""
    code = make_frc(8, beta=2)
    mask = _expand(code, bits)
    grads = np.random.default_rng(seed).normal(size=(4, P))
    out = _decode(code, grads, mask)
    assert np.isfinite(out).all()
    surviving = [cl for cl in range(4) if bits[cl]]
    if surviving:
        assert not decode_exact_possible(code, mask) or len(surviving) == 4
        np.testing.assert_allclose(
            out, grads[surviving].mean(0), rtol=1e-6, atol=1e-9)
    else:
        np.testing.assert_allclose(out, 0.0)         # all erased: hold still
    # monotonicity: any further erasure keeps coverage a subset
    fewer = [v & 0b01 for v in bits]                 # drop the high replica
    kept = {cl for cl in range(4) if fewer[cl]}
    assert kept <= set(surviving)


@settings(max_examples=20, deadline=None)
@given(bits=_cluster_masks(2, 4, allow_empty=True),
       seed=st.integers(0, 2 ** 16))
def test_subk_trajectory_still_descends_its_surviving_objective(bits, seed):
    """Below the decode threshold the iterate optimizes the SURVIVING
    data's objective — and with a step below 1/L that descent is monotone
    per iteration (degradation is objective-wise graceful, never a
    blow-up)."""
    code = make_frc(8, beta=2)
    rng = np.random.default_rng(seed)
    # per-cluster least squares: grad_c(w) = M_c w - r_c with M_c psd
    X = rng.normal(size=(4, 8, P))
    y = rng.normal(size=(4, 8))
    Ms = np.einsum("cnp,cnq->cpq", X, X) / 8.0
    rs = np.einsum("cnp,cn->cp", X, y) / 8.0
    surviving = [cl for cl in range(4) if bits[cl]]
    if not surviving:
        return                                   # all erased: iterate holds
    Msub = Ms[surviving].mean(0)
    rsub = rs[surviving].mean(0)

    def f_sub(w):        # surviving-subset objective (up to a constant)
        return 0.5 * w @ Msub @ w - rsub @ w

    lip = float(np.linalg.eigvalsh(Msub).max())
    step = 0.9 / max(lip, 1e-9)
    mask = _expand(code, bits)
    w = np.zeros(P)
    prev = f_sub(w)
    for _ in range(12):
        g = _decode(code, np.einsum("cpq,q->cp", Ms, w) - rs, mask)
        # decode over survivors == gradient of the surviving objective,
        # rescaled by the survivor fraction (the renormalized mean)
        np.testing.assert_allclose(g, Msub @ w - rsub, rtol=1e-5, atol=1e-8)
        w = w - step * g
        cur = f_sub(w)
        assert cur <= prev + 1e-12               # monotone descent
        prev = cur
