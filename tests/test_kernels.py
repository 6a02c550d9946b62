"""Pallas kernel validation: shape/dtype sweeps vs jnp oracles + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels.fwht import fwht_kernel_call, pick_block_rows
from repro.kernels.coded_reduce import coded_combine_call
from repro.kernels.ref import fwht_ref, fwht_matrix_ref, coded_combine_ref
from repro.kernels.ops import fwht, hadamard_encode, coded_combine


@pytest.mark.parametrize("rows", [1, 8, 32])
@pytest.mark.parametrize("n", [128, 256, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_shapes_dtypes(rows, n, dtype):
    x = jax.random.normal(jax.random.key(0), (rows, n)).astype(dtype)
    out = fwht_kernel_call(x, interpret=True)
    ref = fwht_ref(x).astype(dtype)
    assert out.shape == x.shape and out.dtype == dtype
    tol = 1e-4 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol * np.sqrt(n), rtol=1e-2)


def test_fwht_vs_dense_matrix():
    x = jax.random.normal(jax.random.key(1), (4, 128))
    np.testing.assert_allclose(np.asarray(fwht_kernel_call(x)),
                               np.asarray(fwht_matrix_ref(x)),
                               rtol=1e-4, atol=1e-3)


def test_fwht_block_rows_sweep():
    x = jax.random.normal(jax.random.key(2), (16, 256))
    full = fwht_kernel_call(x, block_rows=16)
    for br in [1, 2, 4, 8]:
        np.testing.assert_allclose(np.asarray(fwht_kernel_call(
            x, block_rows=br)), np.asarray(full), rtol=1e-5, atol=1e-5)


def test_pick_block_rows_fits_budget():
    br = pick_block_rows(4096, 8192, 4, vmem_budget=8 * 2 ** 20)
    assert br * 2 * 8192 * 4 <= 8 * 2 ** 20
    assert br >= 8


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), logn=st.integers(3, 9))
def test_fwht_involution_property(seed, logn):
    """H (H x) = n x — the defining FWHT property (hypothesis)."""
    n = 1 << logn
    x = jax.random.normal(jax.random.key(seed), (2, n))
    twice = fwht_kernel_call(fwht_kernel_call(x))
    np.testing.assert_allclose(np.asarray(twice), n * np.asarray(x),
                               rtol=1e-3, atol=1e-2 * n)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_fwht_linearity(seed):
    k1, k2 = jax.random.split(jax.random.key(seed))
    a = jax.random.normal(k1, (3, 128))
    b = jax.random.normal(k2, (3, 128))
    lhs = fwht_kernel_call(a + 2.0 * b)
    rhs = fwht_kernel_call(a) + 2.0 * fwht_kernel_call(b)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-3)


def test_fwht_rejects_non_pow2():
    with pytest.raises(ValueError):
        fwht_kernel_call(jnp.ones((4, 100)))


def test_fwht_axis_wrapper():
    x = jax.random.normal(jax.random.key(3), (128, 5))
    out = fwht(x, axis=0)
    ref = fwht_ref(x.T).T
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_hadamard_encode_matches_dense():
    import math
    from repro.core.encoding import hadamard_matrix
    rng = np.random.default_rng(1)
    n, p, N = 64, 8, 128
    cols = rng.choice(N, size=n, replace=False)
    signs = rng.choice([-1.0, 1.0], size=n)
    X = rng.standard_normal((n, p)).astype(np.float32)
    S = hadamard_matrix(N)[:, cols] * signs[None, :] / math.sqrt(n)
    out = hadamard_encode(jnp.asarray(X), cols, signs, N=N)
    np.testing.assert_allclose(np.asarray(out), S @ X, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,P", [(4, 128), (16, 2048), (32, 6144)])
def test_coded_combine(m, P):
    g = jax.random.normal(jax.random.key(4), (m, P))
    c = jax.random.uniform(jax.random.key(5), (m,))
    np.testing.assert_allclose(np.asarray(coded_combine_call(
        g, c, block=min(2048, P), interpret=True)),
        np.asarray(coded_combine_ref(g, c)), rtol=1e-5, atol=1e-5)


def test_coded_combine_wrapper_padding():
    g = jax.random.normal(jax.random.key(6), (8, 3000))
    c = jax.random.uniform(jax.random.key(7), (8,))
    np.testing.assert_allclose(np.asarray(coded_combine(g, c)),
                               np.asarray(coded_combine_ref(g, c)),
                               rtol=1e-5, atol=1e-5)
