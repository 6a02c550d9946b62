"""Reductions over the program's spans (``repro.obs.trace.span``).

Two views of one span: its host-clock duration, as ``run.program_spans``
took it (``ctx.spans``, objects with ``name`` and ``dur`` in seconds), and
its event on the profiler's host plane (``DeviceTrace.host``), on the
device trace's clock.  A *leaf* program span is a host event whose name is
a program span's and that contains no other such event; the leaves are
the phases the program names most finely.
"""
from __future__ import annotations


def durations(spans, name: str) -> list:
    """Host-clock seconds of every span called ``name``."""
    return [s.dur for s in spans if s.name == name]


def merge(intervals) -> list:
    """The union of (start, end) intervals as sorted, disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaf_intervals(host, names) -> list:
    """(start, end) of every host event named in ``names`` that contains
    no other such event."""
    evs = sorted(((s, e) for name, s, e in host if name in names),
                 key=lambda iv: (iv[0], -iv[1]))
    leaf = [True] * len(evs)
    open_: list = []                   # indices of events still open
    for i, (s, e) in enumerate(evs):
        while open_ and evs[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= evs[open_[-1]][1]:
            leaf[open_[-1]] = False
        open_.append(i)
    return [iv for iv, is_leaf in zip(evs, leaf) if is_leaf]


def idle_intervals(trace, lo_ns: float, hi_ns: float) -> list:
    """The gaps between operations on the first device, within
    [lo_ns, hi_ns]."""
    busy = merge((max(s, lo_ns), min(e, hi_ns))
                 for _, s, e in trace.ops[trace.devices[0]]
                 if e > lo_ns and s < hi_ns)
    gaps, last = [], lo_ns
    for s, e in busy:
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    if hi_ns > last:
        gaps.append((last, hi_ns))
    return gaps


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def named_idle_share(ctx):
    """100 x the share of the window's device-idle time (first device,
    inside ``chipbench:window``) that lies under a leaf program span;
    None without a device plane, a window or program spans."""
    names = {s.name for s in ctx.spans}
    if not ctx.trace.ops or ctx.lo_ns is None or not names:
        return None
    idle = idle_intervals(ctx.trace, ctx.lo_ns, ctx.hi_ns)
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    leaves = merge(leaf_intervals(ctx.trace.host, names))
    return 100.0 * overlap_ns(idle, leaves) / total
