"""The ridge cells' check at a size a test run holds, on the CPU.

The reference is tied to the program's encoder, the control (the
reference in int8 in the program's place) fails the cell's limits, and a
run with the timed path broken underneath comes out not correct: a step
that returns its state unchanged, half of the workers left out with the
mean taken over the rest, an answer altered where it is produced, an
objective trace one iteration behind, and schedules that break the cell's law (k ignored, one schedule for every
realization).
"""
import numpy as np
import pytest

import dataclasses

import jax

from chipbench import run
from chipbench.reference import ridge as ref
from chipbench.traffic import solve

CELL = "ridge-fig7.gd-mc16"
SMALL = dict(n=64, p=48, m=8, steps=10)
SMALL_WL = dict(trials=4, k=6)


def _run(seed=5, seconds=0.5):
    return run.run_cell(CELL, seed, seconds, False,
                        devices=jax.devices()[:1], wl_override=SMALL_WL,
                        cfg_override=SMALL)


def _session(seed, **wl_override):
    _, _, wl, cfg = run.load_cell(CELL)
    wl.update(SMALL_WL, **wl_override)
    cfg.update(SMALL)
    s = solve.Session(cfg, wl, seed, jax.devices()[:1])
    s.setup()
    return s, wl


@pytest.fixture(autouse=True)
def _fused_kernel(monkeypatch):
    # the kernel path the chip takes, in interpret mode; fresh traces so a
    # patched function is traced in
    monkeypatch.setenv("REPRO_FUSED", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_reference_encode_is_the_programs_encoder():
    from repro.core.encoding import make_encoder
    rng = np.random.default_rng(0)
    X, y = rng.standard_normal((64, 48)), rng.standard_normal(64)
    SX, Sy = ref.encode(X, y, beta=2.0, m=8, seed=0)
    S = make_encoder("hadamard", 64, beta=2.0, seed=0).with_workers(8)
    want = S.materialize() @ np.concatenate([X, y[:, None]], axis=1)
    got = np.concatenate([np.asarray(SX), np.asarray(Sy)[..., None]],
                         axis=-1).reshape(want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["window_compiles"] == 0


def test_traced_run_reads_the_window():
    # no TPU plane on the CPU: device metrics stay silent, spans are read
    res = run.run_cell(CELL, 6, 0.5, True, devices=jax.devices()[:1],
                       wl_override=SMALL_WL, cfg_override=SMALL,
                       peaks={"flops_per_s": 1e12, "bytes_per_s": 1e11})
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert "encode_ms.solve" in res["metrics"]
    assert "fused_roofline" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_fails_the_limits():
    s, wl = _session(11)
    got = s.calibrate()
    limits = wl["check"]["limits"]
    assert all(got["program"][k] <= v for k, v in limits.items())
    assert any(got["control"][k] > v for k, v in limits.items()
               if k in got["control"])


def test_program_spans_leave_the_recorder_off():
    from repro.obs import trace as obs_trace
    from repro.runtime import strategies
    orig = strategies._obs_span
    with run.program_spans() as spans:
        assert obs_trace.current_recorder() is None
        with strategies._obs_span("encode", n=1):
            pass
    assert strategies._obs_span is orig
    assert [s.name for s in spans] == ["encode"] and spans[0].dur >= 0


def _gd_unchanged(monkeypatch):
    from repro.runtime import runners
    monkeypatch.setattr(runners, "_gd_step",
                        lambda prob, w, mask, step, h: w)


def _gd_half(monkeypatch):
    from repro.runtime import runners
    orig = runners._masked_grad

    def half(prob, w, mask):
        m = mask.shape[-1]
        return orig(prob, w, mask * (np.arange(m) < m // 2))
    monkeypatch.setattr(runners, "_masked_grad", half)


def _gd_altered(monkeypatch):
    from repro.runtime import strategies
    orig = strategies.batched_scan_gd

    def altered(*a, **kw):
        w, tr = orig(*a, **kw)
        return w.at[0, 0].add(1.0), tr
    monkeypatch.setattr(strategies, "batched_scan_gd", altered)


def _objective_stale(monkeypatch):
    from repro.runtime import strategies
    orig = strategies.batched_scan_gd

    def stale(*a, **kw):
        w, tr = orig(*a, **kw)
        return w, tr.at[:, 1:].set(tr[:, :-1])
    monkeypatch.setattr(strategies, "batched_scan_gd", stale)


def _schedules(monkeypatch, change):
    from repro.runtime import engine
    orig = engine.ClusterEngine.sample_schedules

    def changed(self, *a, **kw):
        batch = orig(self, *a, **kw)
        return dataclasses.replace(batch, masks=change(batch.masks))
    monkeypatch.setattr(engine.ClusterEngine, "sample_schedules", changed)


def _k_ignored(monkeypatch):
    _schedules(monkeypatch, np.ones_like)


def _one_schedule(monkeypatch):
    _schedules(monkeypatch, lambda m: np.broadcast_to(m[:1], m.shape).copy())


FAULTS = [_gd_unchanged, _gd_half, _gd_altered, _objective_stale,
          _k_ignored, _one_schedule]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
