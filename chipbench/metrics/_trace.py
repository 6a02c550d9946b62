"""Reduction of a ``jax.profiler`` trace to device busy time, kernel time
and idle gaps.

A device plane is one named ``/device:TPU:<i>``; its operations are the
events of its ``XLA Ops`` line.  Busy time is the union of those
intervals; idle share is 1 - busy / window.  Host events (the
``/host:CPU`` plane) label the idle gaps by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceTrace:
    """Per-device operations as (name, start_ns, end_ns), and host events."""
    ops: dict            # device name -> list[(name, start_ns, end_ns)]
    host: list           # list[(name, start_ns, end_ns)]
    lines: dict          # plane name -> sorted line names (diagnostics)

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def find_xplane(logdir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str, device_ids=None) -> DeviceTrace:
    """The trace at ``path``; ``device_ids`` keeps only those TPUs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = {}
    host: list = []
    lines: dict = {}
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        lines[plane.name] = sorted(set(names))
        if _tpu_plane(plane.name, device_ids):
            evs = []
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in ln.events)
            ops[plane.name] = sorted(evs, key=lambda t: t[1])
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events if not e.name.startswith("$"))
    return DeviceTrace(ops=ops, host=sorted(host, key=lambda t: t[1]),
                       lines=lines)


def op_name(event_name: str) -> str:
    """The operation's own name: an XLA op event is named by its whole HLO
    instruction (``%name = type op(operands...)``), whose operand list also
    names the ops it reads; keep what precedes `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _tpu_plane(name: str, device_ids) -> bool:
    if not name.startswith("/device:TPU:"):
        return False
    idx = name[len("/device:TPU:"):]
    if not idx.isdigit():
        return False
    return device_ids is None or int(idx) in device_ids


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: DeviceTrace, lo_ns=None, hi_ns=None) -> float:
    """Seconds in which some operation ran, averaged over the devices;
    intervals are clipped to [lo_ns, hi_ns] where given."""
    if not trace.ops:
        return 0.0
    per = []
    for evs in trace.ops.values():
        iv = []
        for _, s, e in evs:
            if lo_ns is not None:
                s = max(s, lo_ns)
            if hi_ns is not None:
                e = min(e, hi_ns)
            if e > s:
                iv.append((s, e))
        per.append(union_ns(iv) * 1e-9)
    return sum(per) / len(per)


def kernel_events(trace: DeviceTrace, kernel: str) -> list:
    """Durations (s) of every device operation named ``kernel`` or
    ``kernel.<n>``, over all devices."""
    return [(e - s) * 1e-9 for evs in trace.ops.values()
            for name, s, e in evs
            if name == kernel or name.startswith(kernel + ".")]


def top_ops(trace: DeviceTrace, n: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device time,
    summed over devices and divided by their number."""
    tot: dict = {}
    for evs in trace.ops.values():
        for name, s, e in evs:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    k = max(len(trace.ops), 1)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: DeviceTrace, lo_ns: float, hi_ns: float,
              n: int = 10) -> list:
    """[[label, seconds], ...]: the longest gaps between operations on the
    first device, each labelled by the shortest host event that covers
    the gap's midpoint (what the host was doing), else ``"host:?"``."""
    if not trace.ops:
        return []
    evs = trace.ops[trace.devices[0]]
    gaps, last = [], lo_ns
    for _, s, e in evs:
        if s > last:
            gaps.append((last, min(s, hi_ns)))
        last = max(last, e)
    if hi_ns > last:
        gaps.append((last, hi_ns))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(he - hs, name) for name, hs, he in trace.host
                 if hs <= mid <= he]
        label = min(cover)[1] if cover else "host:?"
        out.append([label, (e - s) * 1e-9])
    return out
