"""Device milliseconds per coded training step of the step's collectives
(the gradient's reduction, the weights' all-gather, the scalars' sums), on
the slowest chip: the union of the intervals of the chip's operations
whose opcode in the compiled step's HLO is ``all-reduce``,
``reduce-scatter``, ``all-gather``, ``all-to-all`` or
``collective-permute`` (``traffic/train_mesh`` reads their names from the
HLO text, since the trace names an operation by its instruction name,
which need not name its opcode), in the traced window (an asynchronous
pair counts from its ``-start`` to its ``-done``), over the window's
steps, the largest over the cell's chips."""
from chipbench.metrics import _trace


def collective_intervals(evs, lo_ns, hi_ns, collectives: dict) -> list:
    """(start, end) of the collectives among a device's operations;
    ``collectives``: {instruction name: (opcode, first operand)}."""
    out, started = [], {}
    for name, s, e in evs:
        if name not in collectives:
            continue
        op, arg = collectives[name]
        if op.endswith("-start"):
            started[name] = s
            continue
        if op.endswith("-done"):
            s = started.pop(arg, s)
        out.append((max(s, lo_ns), min(e, hi_ns)))
    return [(s, e) for s, e in out if e > s]


def read(ctx):
    steps = ctx.record.counts.get("steps", 0)
    collectives = getattr(ctx.session, "collectives", None)
    if not steps or not collectives or ctx.lo_ns is None or not ctx.trace.ops:
        return None
    per_chip = [_trace.union_ns(collective_intervals(evs, ctx.lo_ns,
                                                     ctx.hi_ns, collectives))
                for evs in ctx.trace.ops.values()]
    if not any(per_chip):
        return None
    return 1e-6 * max(per_chip) / steps
