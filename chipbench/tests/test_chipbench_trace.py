"""Idle share, kernel time and idle gaps from a synthetic trace."""
import pytest

from chipbench.metrics import _trace
from chipbench.metrics._trace import DeviceTrace


def _trace_two_devices():
    ms = 1_000_000
    ops = {
        "/device:TPU:0": [("_fused_call.7", 0, 4 * ms),
                          ("fusion.1", 3 * ms, 5 * ms),      # overlaps
                          ("_fused_call.7", 8 * ms, 10 * ms)],
        "/device:TPU:1": [("fusion.2", 0, 2 * ms)],
    }
    host = [("chipbench:window", 0, 10 * ms),
            ("encode", 5 * ms, 8 * ms),
            ("PjitFunction(step)", 5 * ms, 6 * ms)]
    return DeviceTrace(ops=ops, host=host, lines={})


def test_union_merges_overlaps():
    assert _trace.union_ns([(0, 4), (3, 5), (8, 10)]) == 7
    assert _trace.union_ns([]) == 0


def test_busy_is_averaged_over_devices_and_clipped():
    t = _trace_two_devices()
    # device 0 busy 7 ms, device 1 busy 2 ms
    assert _trace.busy_s(t) == pytest.approx(4.5e-3)
    assert _trace.busy_s(t, 0, 4_000_000) == pytest.approx(3e-3)


def test_kernel_time_sums_matching_events():
    t = _trace_two_devices()
    assert sum(_trace.kernel_events(t, "_fused_call")) == pytest.approx(6e-3)
    assert _trace.kernel_events(t, "_fused") == []
    assert _trace.kernel_events(t, "fusion.1") == [pytest.approx(2e-3)]


def test_op_name_drops_the_operand_list():
    name = ("%fusion.15 = f32[16,6000]{1,0} fusion(f32[16,1,6000] "
            "%_fused_call.7, f32[16,6000] %w)")
    assert _trace.op_name(name) == "fusion.15"
    assert _trace.op_name("%_fused_call.7 = f32[1] custom-call()") == \
        "_fused_call.7"


def test_top_ops_and_idle_gaps():
    t = _trace_two_devices()
    top = _trace.top_ops(t)
    assert top[0][0] == "_fused_call.7"
    assert top[0][1] == pytest.approx(3e-3)
    gaps = _trace.idle_gaps(t, 0, 10_000_000)
    assert len(gaps) == 1
    label, secs = gaps[0]
    assert secs == pytest.approx(3e-3)
    assert label == "encode"          # the shortest host event covering it


def test_empty_trace_reads_nothing():
    t = DeviceTrace(ops={}, host=[], lines={})
    assert _trace.busy_s(t) == 0.0
    assert _trace.idle_gaps(t, 0, 10) == []
