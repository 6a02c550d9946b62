"""Share of the device's idle time that the program names, in the solve
cells: of the gaps between operations on the first device inside the
traced window, the part that lies under a leaf program span on the
profiler's host plane (``_spans.named_idle_share``; the leaves are
``encode:prepare``, ``encode:transform``, ``encode:readback``,
``encode:upload``, ``sample-schedule``, ``runner:*`` and
``solve:readback``)."""
from chipbench.metrics import _spans


def read(ctx):
    return _spans.named_idle_share(ctx)
