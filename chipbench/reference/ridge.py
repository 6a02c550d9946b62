"""Plain reference of coded ridge GD (arXiv:1803.05397, Sec. 2.1).

Written from the paper and the encoder's definition, importing nothing of
the program:

- encode: S = H_N[:, cols] diag(signs) / sqrt(n), N = next_pow2(beta n),
  with ``cols`` (n of N, without replacement) and then ``signs`` (+-1)
  drawn by ``numpy.random.default_rng(encoder_seed)``; S [X | y] computed
  by a fast Walsh-Hadamard transform (additions only, exact in float32)
  and split into m equal row blocks, one per worker;
- coded GD: g = sum_i c_i (S_i X)^T (S_i X w - S_i y) + lam w with
  c_i = mask_i (m / k) / (n beta), k = |active set|; w <- w - step g;
- objective f(w) = ||X w - y||^2 / (2n) + lam ||w||^2 / 2.

``precision="highest"`` computes every product in float32 at full
precision; ``precision="bf16"`` rounds every product's operands to
bfloat16 (float32 accumulation), the precision the configuration states;
``precision="int8"`` rounds them to int8 levels of one scale per operand
tensor: the control, one step below it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q8(x):
    """x rounded to int8 levels of one per-tensor scale (kept in float32)."""
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def _dot(spec: str, *ops, precision: str):
    if precision == "bf16":
        ops = [o.astype(jnp.bfloat16) for o in ops]
        return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)
    if precision == "int8":
        ops = [_q8(o) for o in ops]
    return jnp.einsum(spec, *ops, precision=HI)


def ensemble(n: int, beta: float, seed: int):
    N = 1 << max(0, int(round(beta * n)) - 1).bit_length()
    rng = np.random.default_rng(seed)
    cols = rng.choice(N, size=n, replace=False)
    signs = rng.choice([-1.0, 1.0], size=n)
    return N, cols, signs


@partial(jax.jit, static_argnames=("N",))
def _fwht_rows(Z, N: int):
    h = 1
    while h < N:
        Z = Z.reshape(N // (2 * h), 2, h, -1)
        Z = jnp.stack([Z[:, 0] + Z[:, 1], Z[:, 0] - Z[:, 1]], axis=1)
        h *= 2
    return Z.reshape(N, -1)


def encode(X, y, *, beta: float, m: int, seed: int):
    """(SX (m, r, p), Sy (m, r)) as float32 device arrays."""
    n, p = X.shape
    N, cols, signs = ensemble(n, beta, seed)
    rows = -(-N // m) * m
    Xy = jnp.concatenate([jnp.asarray(X, jnp.float32),
                          jnp.asarray(y, jnp.float32)[:, None]], axis=1)
    Z = jnp.zeros((N, p + 1), jnp.float32).at[jnp.asarray(cols)].set(
        Xy * jnp.asarray(signs, jnp.float32)[:, None])
    S = _fwht_rows(Z, N) / math.sqrt(n)
    if rows > N:
        S = jnp.concatenate([S, jnp.zeros((rows - N, p + 1), S.dtype)])
    S = S.reshape(m, rows // m, p + 1)
    return S[..., :p], S[..., p]


def objective(X, y, W, lam: float, precision: str):
    """f of each row of W (R, p)."""
    r = _dot("np,Rp->Rn", X, W, precision=precision) - y[None]
    return (0.5 * jnp.sum(r * r, axis=1) / X.shape[0]
            + lam * 0.5 * jnp.sum(W * W, axis=1))


@partial(jax.jit, static_argnames=("lam", "beta", "precision"))
def _gd(SX, Sy, X, y, masks, step, *, lam: float, beta: float,
        precision: str):
    m = SX.shape[0]
    n = X.shape[0]

    def body(W, mask):                    # W (R, p), mask (R, m)
        k = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        c = mask * (m / k) / (n * beta)
        U = _dot("mrp,Rp->Rmr", SX, W, precision=precision) - Sy[None]
        g = _dot("mrp,Rmr->Rp", SX, U * c[:, :, None], precision=precision)
        W = W - step * (g + lam * W)
        return W, objective(X, y, W, lam, precision)

    W0 = jnp.zeros((masks.shape[0], SX.shape[-1]), jnp.float32)
    W, f = jax.lax.scan(body, W0, jnp.swapaxes(masks, 0, 1))
    return W, f.T


def gd(SX, Sy, X, y, masks, step_size, *, lam, beta, precision="highest"):
    """Coded GD of every realization in masks (R, T, m) from w = 0;
    returns (w (R, p), objective (R, T)) as host float64 arrays."""
    W, f = _gd(SX, Sy, jnp.asarray(X, jnp.float32),
               jnp.asarray(y, jnp.float32), jnp.asarray(masks, jnp.float32),
               jnp.float32(step_size), lam=float(lam), beta=float(beta),
               precision=precision)
    return np.asarray(W, np.float64), np.asarray(f, np.float64)


def rel_err_rows(got, want) -> float:
    """max over rows of ||got - want|| / ||want||."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    num = np.linalg.norm(got - want, axis=1)
    den = np.maximum(np.linalg.norm(want, axis=1), 1e-30)
    return float(np.max(num / den))
