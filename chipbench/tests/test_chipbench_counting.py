"""The counting functions at the cells' shapes."""
import pytest

from chipbench.metrics import _counting as cnt
from chipbench.peaks import PEAKS, device_peaks

V5E = PEAKS["TPU v5 lite"]


def test_fused_gradient_counts_sx_once_per_call():
    m, r, p = 32, 256, 6000
    f1, b1 = cnt.fused_gradient(m, r, p, 1)
    f16, b16 = cnt.fused_gradient(m, r, p, 16)
    assert f16 == 16 * f1 == 16 * 4 * m * r * p
    sx = 4 * (m * r * p + m * r)
    # the shared operand is read once; only per-realization vectors grow
    assert b1 == sx + 4 * (2 * p + m)
    assert b16 - b1 == 15 * 4 * (2 * p + m)
    assert b16 < 1.01 * sx


def test_solver_iteration_is_one_pass_over_sx_and_x():
    n, p, m, r = 4096, 6000, 32, 256
    f, b = cnt.solver_iteration(n, p, m, r, 16)
    assert b == pytest.approx(4 * (m * r * p + m * r + n * p + n)
                              + 16 * 4 * (2 * p + m) + 16 * 4 * p)
    # bandwidth-bound: the bytes bound is the larger at the ridge shape
    assert b / V5E["bytes_per_s"] > f / V5E["flops_per_s"]
    t = cnt.roofline_s(f, b, V5E)
    assert t == pytest.approx(b / V5E["bytes_per_s"])
    assert 0.3e-3 < t < 0.5e-3


def test_encode_counts_one_pass_in_and_out():
    f, b = cnt.encode(4096, 6000, 8192)
    assert b == 4 * (4096 * 6001 + 8192 * 6001)
    assert f == 8192 * 13 * 6001


def test_combine_counts_stack_read_once():
    P = 254_816_256
    f, b = cnt.combine(4, P)
    assert b == 4 * (4 * P + P + 4)
    assert f == 2 * 4 * P


def test_lm_counts_at_deepseek_widths():
    pc = cnt.lm_params(4096, 1, 32, 128, 11008, 12800)
    assert pc["total"] == 254_816_256
    per_tok = cnt.lm_train_flops_per_token(4096, 1, 32, 128, 11008, 12800,
                                           2048)
    matmul = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 12800 * 4096
    assert per_tok == 6 * matmul + 6 * 2048 * 4096
    # 4,096 distinct tokens a step take ~33 ms at the bf16 peak
    assert 30e-3 < 4096 * per_tok / V5E["flops_per_s"] < 36e-3


def test_unknown_device_is_an_error():
    assert device_peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device_peaks("cpu")
