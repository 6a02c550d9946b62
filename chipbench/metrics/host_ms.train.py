"""Host milliseconds a coded training step leaves the device idle: the
program span ``train:step`` (one loop body of
``train.coded.CodedTrainer.run``) less its child ``train:wait`` (the block
on the step's outputs), summed over the window's steps and divided by
their number."""
from chipbench.metrics import _spans


def read(ctx):
    steps = _spans.durations(ctx.spans, "train:step")
    if not steps:
        return None
    waits = _spans.durations(ctx.spans, "train:wait")
    return 1e3 * (sum(steps) - sum(waits)) / len(steps)
