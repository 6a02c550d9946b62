"""Operator/dense equivalence for the matrix-free encoding layer.

Every ``LinearEncoder`` implementation must agree with its ``materialize()``-d
dense matrix on ``encode``/``decode_t`` (including the adjoint identity
<Sx, y> == <x, S'y>), build identical worker blocks, flow through the
spectrum diagnostics, the problem builders, the streaming encode, and the
full runtime compare harness.
"""
import numpy as np
import pytest

from repro.core import (BlockDiagonalEncoder, FastHadamardEncoder,
                        LinearEncoder, as_dense, brip_constant,
                        hadamard_encoder, make_encoded_problem, make_encoder,
                        masked_gradient, subset_spectrum)
from repro.data import lsq_rows, stream_worker_blocks

OPERATORS = {
    "fast-hadamard": lambda n, seed: FastHadamardEncoder(n, 2.0, seed=seed),
    "block-diagonal": lambda n, seed: BlockDiagonalEncoder(
        n, 2.0, seed=seed, block_size=16),
}


def _tol(enc):
    # FWHT runs in float32 on the kernel path; block-diagonal is exact f64.
    return 5e-5 if isinstance(enc, FastHadamardEncoder) else 1e-12


@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_encode_matches_materialized(name):
    enc = OPERATORS[name](96, seed=3)
    S = enc.materialize()
    X = np.random.default_rng(0).standard_normal((96, 5))
    np.testing.assert_allclose(np.asarray(enc.encode(X)), S @ X,
                               atol=_tol(enc) * np.sqrt(S.shape[0]))


@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_decode_t_matches_materialized(name):
    enc = OPERATORS[name](96, seed=3)
    S = enc.materialize()
    G = np.random.default_rng(1).standard_normal((enc.rows, 4))
    np.testing.assert_allclose(np.asarray(enc.decode_t(G)), S.T @ G,
                               atol=_tol(enc) * np.sqrt(S.shape[0]))


@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_adjoint_identity(name):
    """<S x, y> == <x, S' y> — encode and decode_t are true adjoints."""
    enc = OPERATORS[name](64, seed=7)
    rng = np.random.default_rng(2)
    x = rng.standard_normal(64)
    y = rng.standard_normal(enc.rows)
    lhs = float(np.vdot(np.asarray(enc.encode(x), np.float64), y))
    rhs = float(np.vdot(x, np.asarray(enc.decode_t(y), np.float64)))
    assert lhs == pytest.approx(rhs, rel=1e-4, abs=1e-4)


def test_fast_hadamard_reproduces_dense_construction():
    """Same rng draws as hadamard_encoder: materialize() is bit-identical."""
    fh = FastHadamardEncoder(96, 2.0, seed=5)
    dh = hadamard_encoder(96, 2.0, seed=5)
    assert fh.beta == dh.beta
    np.testing.assert_array_equal(fh.materialize(), dh.S)


def test_fast_hadamard_tight_frame():
    S = FastHadamardEncoder(64, 2.0, seed=0).materialize()
    np.testing.assert_allclose(S.T @ S, 2.0 * np.eye(64), atol=1e-9)


def test_block_diagonal_tight_frame_and_structure():
    enc = BlockDiagonalEncoder(96, 2.0, seed=1, block_size=16)
    S = enc.materialize()
    np.testing.assert_allclose(S.T @ S, enc.beta * np.eye(96), atol=1e-9)
    # genuinely block diagonal: tile (j, j') is zero for j != j'
    rb, nb = enc.base.rows, enc.base.n
    for j in range(enc.B):
        off = S[j * rb:(j + 1) * rb].copy()
        off[:, j * nb:(j + 1) * nb] = 0.0
        assert np.abs(off).max() == 0.0


@pytest.mark.parametrize("name", sorted(OPERATORS))
@pytest.mark.parametrize("m", [8, 6])   # aligned (pow2) and padded fallback
def test_worker_blocks_tile_the_encode(name, m):
    enc = OPERATORS[name](96, seed=4).with_workers(m)
    S = enc.materialize()
    assert S.shape[0] == enc.rows and enc.rows % m == 0
    X = np.random.default_rng(3).standard_normal((96, 3))
    stacked = np.concatenate(
        [np.asarray(enc.worker_block(i, X)) for i in range(m)])
    np.testing.assert_allclose(stacked, S @ X,
                               atol=_tol(enc) * np.sqrt(S.shape[0]))


@pytest.mark.parametrize("name", sorted(OPERATORS))
@pytest.mark.parametrize("m", [8, 6])
def test_encode_partitioned_matches_worker_blocks(name, m):
    """The bulk builder path (one pass for FWHT) == per-worker blocks."""
    enc = OPERATORS[name](96, seed=6).with_workers(m)
    X = np.random.default_rng(8).standard_normal((96, 4))
    bulk = [np.asarray(b) for b in enc.encode_partitioned(X)]
    assert len(bulk) == m
    lazy = [np.asarray(enc.worker_block(i, X)) for i in range(m)]
    for b, l in zip(bulk, lazy):
        np.testing.assert_allclose(b, l, atol=1e-5)


def test_with_workers_idempotent_and_guarded():
    enc = FastHadamardEncoder(64, 2.0).with_workers(8)
    assert enc.with_workers(8) is enc
    with pytest.raises(ValueError):
        enc.with_workers(4)


@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_spectrum_tools_accept_operators(name):
    enc = OPERATORS[name](96, seed=0)
    ev = subset_spectrum(enc, 8, 6, trials=5)
    assert ev.shape == (5, 96) and np.isfinite(ev).all()
    eps = brip_constant(enc, 8, 6, trials=5)
    assert 0.0 <= eps < 1.5


@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_encoded_problem_operator_matches_dense(name):
    """make_encoded_problem via worker_block == the dense S route, and the
    masked gradients agree."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    n, p, m = 96, 24, 8
    X = rng.standard_normal((n, p))
    y = X @ rng.standard_normal(p)
    op = OPERATORS[name](n, seed=2)
    prob_op = make_encoded_problem(X, y, op, m, lam=0.01)
    prob_de = make_encoded_problem(X, y, as_dense(op.with_workers(m)), m,
                                   lam=0.01)
    np.testing.assert_allclose(np.asarray(prob_op.SX),
                               np.asarray(prob_de.SX), atol=1e-4)
    w = jnp.asarray(rng.standard_normal(p), jnp.float32)
    mask = jnp.asarray(np.r_[np.ones(m - 2), 0.0, 0.0], jnp.float32)
    np.testing.assert_allclose(np.asarray(masked_gradient(prob_op, w, mask)),
                               np.asarray(masked_gradient(prob_de, w, mask)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("encoder", ["fast-hadamard", "block-diagonal"])
def test_compare_harness_operator_trace_matches_dense(encoder):
    """Acceptance: operator encoders through runtime/compare.py reproduce the
    DenseEncoder objective trace to 1e-4 on a shared delay realization."""
    from repro.runtime.engine import ClusterEngine, make_delay_model
    from repro.runtime.strategies import ProblemSpec, get_strategy
    spec = ProblemSpec.synthetic(n=128, p=32, lam=0.05, seed=0)
    op = make_encoder(encoder, spec.n).with_workers(8)
    traces = {}
    for tag, enc in [("operator", op), ("dense", as_dense(op))]:
        engine = ClusterEngine(make_delay_model("bimodal"), 8, seed=0)
        res = get_strategy("coded-gd").run(spec, engine, steps=40, k=6,
                                           encoder=enc)
        traces[tag] = np.asarray(res.objective)
    np.testing.assert_allclose(traces["operator"], traces["dense"], atol=1e-4)


def test_compare_matrix_accepts_operator_encoders_by_name():
    from repro.runtime.compare import run_matrix
    recs = run_matrix(["coded-gd"], ["bimodal"], n=64, p=16, m=4, k=3,
                      steps=10, encoder="fast-hadamard", seed=1)
    assert len(recs) == 1
    assert recs[0]["meta"]["encoder"] == "fast-hadamard"
    assert np.isfinite(recs[0]["final_objective"])


def test_lsq_rows_deterministic_and_order_free():
    X_all, y_all, w = lsq_rows(0, 300, 8, seed=9)
    X_mid, y_mid, w2 = lsq_rows(100, 200, 8, seed=9)
    np.testing.assert_array_equal(X_mid, X_all[100:200])
    np.testing.assert_array_equal(y_mid, y_all[100:200])
    np.testing.assert_array_equal(w, w2)
    assert lsq_rows(5, 5, 8, seed=9)[0].shape == (0, 8)


def test_stream_worker_blocks_matches_bulk_encode():
    """Worker-by-worker streaming encode == one-shot encode of the full X;
    for block-diagonal each worker only ever pulls its own shard."""
    n, p, m = 128, 6, 8
    enc = BlockDiagonalEncoder(n, 2.0, seed=0, block_size=16).with_workers(m)
    X_full, _, _ = lsq_rows(0, n, p, seed=4)
    S = enc.materialize()
    pulls = []

    def rows_fn(lo, hi):
        pulls.append(hi - lo)
        return lsq_rows(lo, hi, p, seed=4)[0]

    blocks = dict(stream_worker_blocks(enc, m, rows_fn))
    stacked = np.concatenate([blocks[i] for i in range(m)])
    np.testing.assert_allclose(stacked, S @ X_full, atol=1e-10)
    assert max(pulls) < n            # never pulled the whole dataset at once


# ---------------------------------------------------------------------------
# Fused encode kernel (kernels/encode.py)
# ---------------------------------------------------------------------------

def _srht_oracle(n, p, N, seed):
    import math
    from repro.core.encoding import hadamard_matrix
    rng = np.random.default_rng(seed)
    cols = rng.choice(N, size=n, replace=False)
    signs = rng.choice([-1.0, 1.0], size=n)
    X = rng.standard_normal((n, p)).astype(np.float32)
    S = hadamard_matrix(N)[:, cols] * signs[None, :] / math.sqrt(n)
    return X, cols, signs, S


@pytest.mark.parametrize("lo,hi", [(0, 256), (0, 32), (96, 160), (224, 256)])
def test_srht_encode_row_windows(lo, hi):
    """The fused sign-flip + FWHT + gather kernel matches the dense slice."""
    import jax.numpy as jnp
    from repro.kernels.ops import srht_encode
    X, cols, signs, S = _srht_oracle(100, 7, 256, seed=11)
    out = srht_encode(jnp.asarray(X), cols, signs, 256, lo=lo, hi=hi)
    assert out.shape == (hi - lo, 7)
    np.testing.assert_allclose(np.asarray(out), (S @ X)[lo:hi],
                               rtol=1e-4, atol=1e-4)


def test_srht_encode_call_fuses_signs():
    """dsigns zeros must kill dead lanes even if the input has junk there."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.encode import srht_encode_call
    from repro.kernels.ref import fwht_ref
    rows, N = 8, 128
    x = jax.random.normal(jax.random.key(8), (rows, N))
    d = np.zeros((1, N), np.float32)
    d[0, np.arange(0, N, 2)] = 1.0
    out = srht_encode_call(x, jnp.asarray(d), lo=0, hi=N, scale=1.0,
                           interpret=True)
    ref = fwht_ref(x * jnp.asarray(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_srht_encode_call_validates():
    import jax.numpy as jnp
    from repro.kernels.encode import srht_encode_call
    x = jnp.ones((4, 128))
    d = jnp.ones((1, 128))
    with pytest.raises(ValueError):
        srht_encode_call(jnp.ones((4, 100)), jnp.ones((1, 100)), lo=0,
                         hi=100, scale=1.0, interpret=True)
    with pytest.raises(ValueError):
        srht_encode_call(x, d, lo=64, hi=32, scale=1.0, interpret=True)
    with pytest.raises(ValueError):
        srht_encode_call(x, jnp.ones((1, 64)), lo=0, hi=128, scale=1.0,
                         interpret=True)


def test_token_stream_vectorized_motifs():
    """Vectorized sampler: deterministic per seed, right shapes/dtype, and
    motifs actually appear as contiguous subsequences."""
    from repro.data import TokenStream
    ts = TokenStream(64, seed=0, motif_len=8, n_motifs=4)
    a = ts.sample(np.random.default_rng(7), 64, 24)
    b = ts.sample(np.random.default_rng(7), 64, 24)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (64, 25) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 64
    hits = 0
    for row in a:
        for mo in ts._motifs:
            s = mo[:8].astype(np.int32)
            for start in range(25 - 8 + 1):
                if np.array_equal(row[start:start + 8], s):
                    hits += 1
                    break
            else:
                continue
            break
    assert 10 <= hits  # ~50% of 64 rows carry a motif
