"""The whole solve's share of the chip's roofline: the least time the
chip needs for the window's completed solves (per iteration one pass over
SX and one over X for all realizations, ``_counting.solver_iteration``,
plus one encode per solve, ``_counting.encode``) over the traced window.
Ridge is bandwidth-bound, so this is a share of the bandwidth bound."""
from chipbench.metrics import _counting


def read(ctx):
    solves = ctx.record.counts.get("solves", 0)
    if not solves or ctx.window_s <= 0:
        return None
    c, wl = ctx.cfg, ctx.wl
    rows = ctx.session.rows_per_worker
    it = _counting.roofline_s(*_counting.solver_iteration(
        c["n"], c["p"], c["m"], rows, wl["trials"]), ctx.peaks)
    enc = _counting.roofline_s(*_counting.encode(
        c["n"], c["p"], rows * c["m"]), ctx.peaks)
    return 100.0 * solves * (c["steps"] * it + enc) / ctx.window_s
