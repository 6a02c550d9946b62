"""Property tests for the encoded data-parallel gradient machinery."""
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import (hadamard_encoder, gaussian_encoder, identity_encoder,
                        make_encoded_problem, masked_gradient, gd_step,
                        original_objective)


def _problem(enc_fn, n=128, p=32, m=8, lam=0.0, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p))
    y = X @ rng.standard_normal(p) + 0.1 * rng.standard_normal(n)
    return make_encoded_problem(X, y, enc_fn(n), m, lam=lam), X, y


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999))
def test_full_mask_tight_frame_exact_gradient(seed):
    """With k = m and a tight frame, the encoded gradient EQUALS the true
    gradient of the original smooth loss (paper §4.1 optimality argument)."""
    prob, X, y = _problem(lambda n: hadamard_encoder(n, 2.0), seed=seed)
    rng = np.random.default_rng(seed + 1)
    w = jnp.asarray(rng.standard_normal(X.shape[1]))
    g_enc = masked_gradient(prob, w, jnp.ones(prob.m))
    g_true = jnp.asarray(X.T @ (X @ np.asarray(w) - y) / X.shape[0])
    np.testing.assert_allclose(np.asarray(g_enc), np.asarray(g_true),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), drop=st.integers(1, 3))
def test_masked_gradient_bounded_error(seed, drop):
    """Fastest-k gradient error stays within the empirical BRIP envelope:
    ||g~ - g|| <= eps_hat * (||g|| + L ||w||)-ish; we assert the cheap form
    ||g~ - g|| <= 1.5 ||g|| + small for the Hadamard ensemble at eta=5/8."""
    prob, X, y = _problem(lambda n: hadamard_encoder(n, 2.0), seed=seed)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(X.shape[1]) * 0.5)
    mask = np.ones(prob.m)
    mask[rng.choice(prob.m, size=drop, replace=False)] = 0.0
    g_enc = np.asarray(masked_gradient(prob, w, jnp.asarray(mask)))
    g_true = X.T @ (X @ np.asarray(w) - y) / X.shape[0]
    err = np.linalg.norm(g_enc - g_true)
    scale = np.linalg.norm(g_true) + np.linalg.norm(
        X.T @ X / X.shape[0], 2) * np.linalg.norm(np.asarray(w))
    assert err <= 1.5 * scale


def test_uncoded_full_mask_also_exact():
    prob, X, y = _problem(identity_encoder)
    w = jnp.asarray(np.random.default_rng(0).standard_normal(X.shape[1]))
    g = masked_gradient(prob, w, jnp.ones(prob.m))
    g_true = X.T @ (X @ np.asarray(w) - y) / X.shape[0]
    np.testing.assert_allclose(np.asarray(g), g_true, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 99))
def test_gd_step_descends(seed):
    """A small encoded GD step never increases the encoded objective by
    more than the paper's kappa factor — and usually decreases f."""
    prob, X, y = _problem(lambda n: hadamard_encoder(n, 2.0), lam=0.05,
                          seed=seed)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(X.shape[1]))
    L = np.linalg.eigvalsh(X.T @ X / X.shape[0]).max()
    mask = np.ones(prob.m)
    mask[rng.integers(prob.m)] = 0.0
    f0 = float(original_objective(prob, w, h="l2"))
    w1 = gd_step(prob, w, jnp.asarray(mask), 0.2 / (L + 0.05), h="l2")
    f1 = float(original_objective(prob, w1, h="l2"))
    assert f1 <= 1.05 * f0


def test_adamw_quadratic_convergence():
    """Optimizer substrate sanity: AdamW minimizes a quadratic."""
    import jax
    from repro.optim import adamw_init, adamw_update
    A = jnp.asarray(np.diag(np.linspace(1, 5, 8)))
    b = jnp.asarray(np.random.default_rng(0).standard_normal(8))
    params = {"w": jnp.zeros(8)}
    opt = adamw_init(params)
    loss = lambda p: 0.5 * p["w"] @ A @ p["w"] - b @ p["w"]
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(g, opt, params, lr=5e-2,
                                      weight_decay=0.0)
    w_star = jnp.linalg.solve(A, b)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(w_star),
                               atol=5e-2)
