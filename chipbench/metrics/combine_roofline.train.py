"""Share of its roofline that the masked-combine kernel
(``kernels/coded_reduce.py``, ``coded_combine_call``) reaches in the coded
train step: the least time the chip needs to read the (m, P) f32 gradient
stack and write the P combined values (``_counting.combine``) over the
kernel's device time in the trace."""
from chipbench.metrics import _counting, _trace

KERNEL = "coded_combine_call"   # kernels/coded_reduce.py


def read(ctx):
    durs = _trace.kernel_events(ctx.trace, KERNEL)
    if not durs:
        return None
    m = ctx.cfg["trainer"]["m_workers"]
    flops, nbytes = _counting.combine(m, ctx.session.n_params)
    ideal = len(durs) * _counting.roofline_s(flops, nbytes, ctx.peaks)
    return 100.0 * ideal / sum(durs)
