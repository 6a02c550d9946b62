"""Host-clock milliseconds per solve that the encode spends moving the
encoded problem between host and device: the program spans
``encode:readback`` (the encoded blocks to host f64 and their stack) and
``encode:upload`` (SX, Sy, X, y to the device), children of ``encode``
(``core.data_parallel.make_encoded_problem``), summed over the window and
divided by the number of ``encode`` spans."""
from chipbench.metrics import _spans


def read(ctx):
    encodes = _spans.durations(ctx.spans, "encode")
    moves = (_spans.durations(ctx.spans, "encode:readback")
             + _spans.durations(ctx.spans, "encode:upload"))
    if not encodes or not moves:
        return None
    return 1e3 * sum(moves) / len(encodes)
