"""The readers of the program's phase spans and its host-sync counter: the
leaf-coverage reduction on a hand-built trace, readers that stay silent on
a program without the spans, and traced CPU runs of both cells."""
import types

import jax
import pytest

from chipbench import run
from chipbench.metrics import _spans
from chipbench.metrics._trace import DeviceTrace
from chipbench.tests import test_chipbench_lm as lm
from chipbench.tests import test_chipbench_ridge as ridge

MS = 1_000_000
NAMES = {"encode", "encode:prepare", "encode:readback",
         "sample-schedules", "sample-schedule"}
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
NEW = ("sample_ms.solve", "encode_roundtrip_ms.solve", "idle_named.solve",
       "host_ms.train", "syncs_per_step.train", "idle_named.train")


def _hand_trace():
    """Device 0 busy [0,1], [7,8], [15,20] ms of a [0,20] ms window: idle
    [1,7] and [8,15], 13 ms.  ``encode`` [0,10] holds two leaves; a gap
    under ``encode`` alone, one under no span, and a host event of JAX's
    own inside a leaf."""
    ops = {"/device:TPU:0": [("a", 0, 1 * MS), ("b", 7 * MS, 8 * MS),
                             ("c", 15 * MS, 20 * MS)],
           "/device:TPU:1": [("d", 0, 20 * MS)]}
    host = [("chipbench:window", 0, 20 * MS),
            ("encode", 0, 10 * MS),
            ("encode:prepare", 1 * MS, 3 * MS),
            ("ParseArguments", 1 * MS, 2 * MS),
            ("encode:readback", 4 * MS, 6 * MS),
            ("sample-schedules", 11 * MS, 13 * MS),
            ("sample-schedule", 11 * MS, 12 * MS),
            ("sample-schedule", 12 * MS, 13 * MS)]
    return DeviceTrace(ops=ops, host=host, lines={})


def _ctx(trace, names, session=None):
    spans = [types.SimpleNamespace(name=n, dur=1e-3) for n in names]
    return types.SimpleNamespace(trace=trace, lo_ns=0, hi_ns=20 * MS,
                                 spans=spans, session=session)


def test_leaves_are_the_innermost_program_spans():
    leaves = _spans.leaf_intervals(_hand_trace().host, NAMES)
    assert leaves == [(1 * MS, 3 * MS), (4 * MS, 6 * MS),
                      (11 * MS, 12 * MS), (12 * MS, 13 * MS)]


def test_idle_time_under_leaves():
    t = _hand_trace()
    assert _spans.idle_intervals(t, 0, 20 * MS) == [(1 * MS, 7 * MS),
                                                    (8 * MS, 15 * MS)]
    # 4 ms of leaves in the first gap, 2 ms in the second; the rest lies
    # under ``encode`` alone or under no span
    share = _spans.named_idle_share(_ctx(t, NAMES))
    assert share == pytest.approx(100.0 * 6 / 13)


def test_overlap_and_merge():
    assert _spans.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert _spans.overlap_ns([(0, 3), (5, 9)], [(2, 6)]) == 2
    assert _spans.overlap_ns([], [(0, 1)]) == 0


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_without_their_spans(name):
    # the program as it was before these spans: ``encode``, ``train:coded``
    # and ``sample-schedule`` alone, no counters, no device plane
    ctx = _ctx(DeviceTrace(ops={}, host=[], lines={}),
               ["encode", "sample-schedule", "train:coded"],
               session=types.SimpleNamespace(trainer=object()))
    assert run.load_reader(name)(ctx) is None


def test_span_readers_on_hand_spans():
    spans = [("encode", 0.5), ("encode:readback", 0.1),
             ("encode:upload", 0.05), ("encode", 0.7),
             ("encode:readback", 0.2), ("encode:upload", 0.05),
             ("sample-schedules", 0.01), ("sample-schedules", 0.03),
             ("train:step", 0.3), ("train:wait", 0.25),
             ("train:step", 0.2), ("train:wait", 0.15)]
    ctx = types.SimpleNamespace(spans=[types.SimpleNamespace(name=n, dur=d)
                                       for n, d in spans])
    read = run.load_reader
    assert read("encode_roundtrip_ms.solve")(ctx) == pytest.approx(200.0)
    assert read("sample_ms.solve")(ctx) == pytest.approx(20.0)
    assert read("host_ms.train")(ctx) == pytest.approx(50.0)


@pytest.fixture
def fused_kernel(monkeypatch):
    # the ridge cell's kernel path on the chip, in interpret mode
    monkeypatch.setenv("REPRO_FUSED", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _check_traced(res, want):
    assert res["correct"], res["checks"]
    got = {k for k, v in res["metrics"].items() if v["value"] > 0}
    assert want <= got
    # no TPU plane on the CPU: the device metrics stay silent
    assert not {"idle_named.solve", "idle_named.train"} & set(res["metrics"])


def test_traced_ridge_run_reports_the_program_metrics(fused_kernel):
    res = run.run_cell(ridge.CELL, 7, 0.5, True, devices=jax.devices()[:1],
                       wl_override=ridge.SMALL_WL, cfg_override=ridge.SMALL,
                       peaks=PEAKS)
    _check_traced(res, {"sample_ms.solve", "encode_roundtrip_ms.solve"})


def test_traced_lm_run_reports_the_program_metrics():
    res = run.run_cell(lm.CELL, 7, 0.5, True, devices=jax.devices()[:1],
                       wl_override=lm.WL, cfg_override=lm.SMALL, peaks=PEAKS)
    _check_traced(res, {"host_ms.train", "syncs_per_step.train"})
    assert res["metrics"]["syncs_per_step.train"]["value"] == 4
