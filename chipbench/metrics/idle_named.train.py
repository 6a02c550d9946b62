"""Share of the device's idle time that the program names, in the training
cells: of the gaps between operations on the first device inside the
traced window, the part that lies under a leaf program span on the
profiler's host plane (``_spans.named_idle_share``; the leaves are the
children of ``train:step``: ``train:batch``, ``train:decode``,
``train:dispatch``, ``train:wait``, ``train:readback``,
``train:callback``)."""
from chipbench.metrics import _spans


def read(ctx):
    return _spans.named_idle_share(ctx)
