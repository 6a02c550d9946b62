"""Mean host-clock milliseconds of the program's ``sample-schedules`` span
per solve: the engine's draw of the solve's (R, T, m) straggler schedules
and their stack (``runtime.engine.ClusterEngine.sample_schedules``), one a
solve."""
from chipbench.metrics import _spans


def read(ctx):
    durs = _spans.durations(ctx.spans, "sample-schedules")
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
