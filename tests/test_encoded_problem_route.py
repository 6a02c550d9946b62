"""The two routes of ``make_encoded_problem``, chosen by ``enc.on_device``.

A device encoder (``fast-hadamard``) keeps the encoded problem on the
device: no block is read back, and SX, Sy, X and y are bit for bit what
the host assembly (blocks to float64, ``np.stack``, back to float32) gave.
Host encoders keep that assembly unchanged.
"""
import jax
import numpy as np
import pytest

from repro.core import (FastHadamardEncoder, LinearEncoder,
                        make_encoded_problem, make_encoder)


class _HostFastHadamard(FastHadamardEncoder):
    """The same operator, routed through the host."""
    on_device = False


def _data(n, p, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p)).astype(dtype)
    return X, (X @ rng.standard_normal(p)).astype(dtype)


def _host_assembly(X, y, enc, m):
    """SX, Sy, X, y as the host route builds them: [X|y] in float64, the
    worker blocks read back to float64 and stacked, then float32."""
    enc = enc.with_workers(m)
    Xy = np.concatenate([np.asarray(X, np.float64),
                         np.asarray(y, np.float64)[:, None]], axis=1)
    SXy = np.stack([np.asarray(b, np.float64)
                    for b in enc.encode_partitioned(Xy)])
    return (np.asarray(SXy[..., :-1], np.float32),
            np.asarray(SXy[..., -1], np.float32),
            np.asarray(X, np.float32), np.asarray(y, np.float32))


def _assert_bits_equal(prob, ref):
    got = [np.asarray(a) for a in (prob.SX, prob.Sy, prob.X, prob.y)]
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype == np.float32 and g.shape == r.shape
        np.testing.assert_array_equal(g.view(np.uint32), r.view(np.uint32))


def _spy_host_assembly(monkeypatch):
    """Record each call of the host assembly's pieces: the per-worker
    block builder, ``np.stack`` and ``jax.device_get``."""
    calls = []

    def wrap(owner, name, label):
        orig = getattr(owner, name)

        def spy(*a, **kw):
            calls.append(label)
            return orig(*a, **kw)
        monkeypatch.setattr(owner, name, spy)

    wrap(np, "stack", "stack")
    wrap(jax, "device_get", "device_get")
    wrap(LinearEncoder, "encode_partitioned", "encode_partitioned")
    wrap(FastHadamardEncoder, "encode_partitioned", "encode_partitioned")
    return calls


def test_route_is_a_class_attribute():
    assert FastHadamardEncoder.on_device is True
    assert LinearEncoder.on_device is False
    for name in ("hadamard", "gaussian", "uncoded", "block-diagonal"):
        assert make_encoder(name, 32).on_device is False


@pytest.mark.parametrize("n,m,dtype", [
    (64, 8, np.float32),    # aligned: N = 128 rows, 16 a worker
    (48, 3, np.float64),    # n not a power of two; 128 rows + 1 zero row
    (100, 6, np.float32),   # 256 rows + 2 zero rows
    (100, 7, np.float64),   # 256 rows + 3 zero rows
])
def test_device_route_matches_host_assembly_bitwise(n, m, dtype,
                                                     monkeypatch):
    X, y = _data(n, 5, dtype, seed=n + m)
    enc = FastHadamardEncoder(n, 2.0, seed=3)
    ref = _host_assembly(X, y, enc, m)
    with monkeypatch.context() as mp:
        calls = _spy_host_assembly(mp)
        prob = make_encoded_problem(X, y, enc, m, lam=0.1)
        assert calls == []
    for a in (prob.SX, prob.Sy, prob.X, prob.y):
        assert isinstance(a, jax.Array)
    bound = enc.with_workers(m)
    assert prob.SX.shape == (m, bound.rows_per_worker, 5)
    if bound._pad:                       # the last worker's zero rows
        assert not np.asarray(prob.SX)[-1, -bound._pad:].any()
    _assert_bits_equal(prob, ref)
    assert (prob.n, prob.beta, prob.lam) == (n, enc.beta, 0.1)


@pytest.mark.parametrize("make", [
    lambda n: make_encoder("hadamard", n, seed=1),
    lambda n: make_encoder("gaussian", n, seed=1),
    lambda n: make_encoder("block-diagonal", n, seed=1, block_size=16),
    lambda n: _HostFastHadamard(n, 2.0, seed=1),
], ids=["hadamard", "gaussian", "block-diagonal", "fast-hadamard-on-host"])
def test_host_route_keeps_the_float64_assembly(make, monkeypatch):
    X, y = _data(48, 5, np.float64, seed=7)
    enc = make(48)
    with monkeypatch.context() as mp:
        calls = _spy_host_assembly(mp)
        prob = make_encoded_problem(X, y, enc, 6)
        assert calls.count("encode_partitioned") == 1
        assert calls.count("stack") == 1
    _assert_bits_equal(prob, _host_assembly(X, y, enc, 6))


def test_both_routes_give_one_problem():
    X, y = _data(100, 5, np.float32, seed=2)
    dev = make_encoded_problem(X, y, FastHadamardEncoder(100, 2.0, seed=4), 6)
    host = make_encoded_problem(X, y, _HostFastHadamard(100, 2.0, seed=4), 6)
    _assert_bits_equal(dev, [np.asarray(a) for a in
                             (host.SX, host.Sy, host.X, host.y)])
