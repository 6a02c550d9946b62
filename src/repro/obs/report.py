"""``python -m repro.obs.report`` — text report over a saved trace.

Renders the two views the paper's tail-latency story needs from a
``TraceRecorder`` JSONL export (``repro.experiments.run --trace``):

  * **phase breakdown** — host-clock span totals by name (calls, total
    seconds, mean, share), so "where does per-step time go" (encode vs
    solve vs sampling) is one glance;
  * **straggler timeline** — per (cell, realization) lane group: per-worker
    miss counts with a bar chart, active-set-size stats, and the first
    iterations as an ASCII lane diagram (``#`` active, ``.`` erased);
  * **async summary** — staleness histogram + drop/clamp counts for
    per-arrival cells;
  * **fault timeline** — for fault-injected runs (``--faults``): per-kind
    event counts (crash / blackout / corrupt), the failed-entry share of
    the (iteration, worker) grid, and the first fault events in time
    order.

    PYTHONPATH=src python -m repro.obs.report runs/exp/trace.jsonl \\
        [--max-steps 24] [--cell SUBSTR]
"""
from __future__ import annotations

import argparse
from collections import defaultdict
from typing import Sequence

import numpy as np

from .trace import TraceRecorder

__all__ = ["phase_breakdown", "render_report", "main"]

_BAR = 28


def _bar(frac: float, width: int = _BAR) -> str:
    n = int(round(max(0.0, min(1.0, frac)) * width))
    return "#" * n + "." * (width - n)


def phase_breakdown(events) -> list[tuple]:
    """Aggregate span events by name -> sorted [(name, calls, total_s,
    mean_s, share)] rows (share of the summed span time; spans nest, so
    shares can exceed 1 in total)."""
    agg: dict = defaultdict(lambda: [0, 0.0])
    for ev in events:
        if ev.kind == "span":
            agg[ev.name][0] += 1
            agg[ev.name][1] += ev.dur
    total = sum(v[1] for v in agg.values()) or 1.0
    rows = [(name, calls, secs, secs / calls, secs / total)
            for name, (calls, secs) in agg.items()]
    return sorted(rows, key=lambda r: -r[2])


def _lane_groups(events) -> dict:
    """(cell, realization) -> {"iter": [...], "worker": [...], ...}."""
    groups: dict = defaultdict(lambda: defaultdict(list))
    for ev in events:
        if ev.kind in ("iter", "worker", "update", "instant"):
            groups[(ev.cell, ev.realization)][ev.kind].append(ev)
    return groups


def _render_sync_group(out, iters, workers, max_steps: int) -> None:
    m = 1 + max(int(ev.lane.split(":", 1)[1]) for ev in workers)
    steps = sorted({ev.step for ev in iters})
    active = np.zeros((len(steps), m), dtype=bool)
    index = {t: j for j, t in enumerate(steps)}
    for ev in workers:
        active[index[ev.step], int(ev.lane.split(":", 1)[1])] = \
            bool(ev.args.get("active", True))
    miss = 1.0 - active.mean(axis=0)
    sizes = active.sum(axis=1)
    durs = [ev.dur for ev in iters]
    out.append(f"  iterations={len(steps)} workers={m} "
               f"active_size mean={sizes.mean():.2f} "
               f"min={sizes.min()} max={sizes.max()}")
    out.append(f"  step latency s: p50={np.percentile(durs, 50):.4f} "
               f"p95={np.percentile(durs, 95):.4f} "
               f"p99={np.percentile(durs, 99):.4f}")
    out.append("  per-worker miss-rate:")
    for i in range(m):
        out.append(f"    worker {i:3d} {_bar(miss[i])} {miss[i]:6.1%}")
    shown = steps[:max_steps]
    out.append(f"  lanes (first {len(shown)} iterations; # active, "
               f". erased):")
    for t in shown:
        row = "".join("#" if active[index[t], i] else "."
                      for i in range(m))
        out.append(f"    iter {t:4d} |{row}|")


def _fault_summary(workers, instants):
    """Fault view of one lane group: per-kind event counts, the event
    timeline, and the failed-entry share of the (iteration, worker) grid.
    Everything is empty when the trace carries no fault lane."""
    events = [ev for ev in instants if ev.name.startswith("fault:")]
    counts: dict = {}
    for ev in events:
        kind = ev.args.get("fault", ev.name.split(":", 1)[1])
        counts[kind] = counts.get(kind, 0) + 1
    frac: dict = {}
    if workers:
        by_kind: dict = {}
        for ev in workers:
            code = ev.args.get("failed")
            if code is not None:
                by_kind[code] = by_kind.get(code, 0) + 1
        frac = {k: v / len(workers) for k, v in sorted(by_kind.items())}
    return counts, events, frac


def _render_fault_group(out, workers, instants, max_events: int = 12) -> None:
    counts, events, frac = _fault_summary(workers, instants)
    if not counts and not frac:
        return
    head = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    out.append(f"  faults: {head or '(failed codes only)'}")
    if frac:
        out.append("  failed share of (iteration, worker) grid: "
                   + " ".join(f"{k}={v:.1%}" for k, v in frac.items()))
    for ev in sorted(events, key=lambda e: e.ts)[:max_events]:
        dur = ev.args.get("duration_s")
        tail = f" dur={dur:.2f}s" if dur else ""
        out.append(f"    t={ev.ts:8.3f} {ev.lane:10s} "
                   f"{ev.args.get('fault', ev.name)}{tail}")
    if len(events) > max_events:
        out.append(f"    ... {len(events) - max_events} more fault events")


def _render_async_group(out, updates, instants) -> None:
    stale = np.asarray([ev.args.get("staleness", 0) for ev in updates])
    out.append(f"  updates={stale.size} mean_staleness={stale.mean():.2f} "
               f"max={stale.max()}")
    vals, cnts = np.unique(stale, return_counts=True)
    peak = cnts.max()
    out.append("  staleness histogram:")
    for v, c in zip(vals, cnts):
        out.append(f"    tau={int(v):3d} {_bar(c / peak)} {int(c)}")
    for ev in instants:
        if ev.name == "async-summary":
            out.append(f"  dropped={ev.args.get('dropped', 0)} "
                       f"staleness_clamped="
                       f"{ev.args.get('staleness_clamped', 0)}")


def render_report(rec: TraceRecorder, *, max_steps: int = 24,
                  cell: str | None = None) -> str:
    """The full text report for a loaded trace."""
    events = rec.events()
    out: list[str] = []
    if rec.meta:
        out.append(f"trace meta: {rec.meta}")
    rows = phase_breakdown(events)
    if rows:
        out.append("")
        out.append("phase breakdown (host spans):")
        out.append(f"  {'phase':24s} {'calls':>6s} {'total_s':>10s} "
                   f"{'mean_ms':>9s} {'share':>7s}")
        for name, calls, secs, mean, share in rows:
            out.append(f"  {name:24s} {calls:6d} {secs:10.4f} "
                       f"{mean * 1e3:9.3f} {share:7.1%}")
    for (cell_name, r), kinds in sorted(
            _lane_groups(events).items(),
            key=lambda kv: (str(kv[0][0]), kv[0][1])):
        if cell is not None and cell not in str(cell_name):
            continue
        out.append("")
        out.append(f"straggler timeline — cell={cell_name or 'run'} "
                   f"realization={r}")
        if kinds.get("iter"):
            _render_sync_group(out, kinds["iter"], kinds.get("worker", []),
                               max_steps)
        if kinds.get("update"):
            _render_async_group(out, kinds["update"],
                                kinds.get("instant", []))
        _render_fault_group(out, kinds.get("worker", []),
                            kinds.get("instant", []))
    if len(out) <= 1 and not rows:
        out.append("(trace contains no span or simulation events)")
    return "\n".join(out)


def main(argv: Sequence[str] | None = None) -> str:
    ap = argparse.ArgumentParser(
        prog="repro.obs.report",
        description="straggler-timeline + phase-breakdown report from a "
                    "saved obs trace (JSONL)")
    ap.add_argument("trace", help="path to a TraceRecorder JSONL export")
    ap.add_argument("--max-steps", type=int, default=24,
                    help="iterations to draw per lane diagram")
    ap.add_argument("--cell", default=None,
                    help="only render timelines whose cell label contains "
                         "this substring")
    args = ap.parse_args(argv)
    rec = TraceRecorder.load(args.trace)
    text = render_report(rec, max_steps=args.max_steps, cell=args.cell)
    print(text)
    return text


if __name__ == "__main__":
    main()
