"""Share of its roofline that the fused masked-gradient kernel
(``kernels/fused_step.py``) reaches: the least time the chip needs for the
calls' work (``_counting.fused_gradient``, SX read once per call however
many realizations the call carries) over the kernel's device time in the
trace."""
from chipbench.metrics import _counting, _trace

KERNEL = "_fused_call"       # the jitted pallas_call of kernels/fused_step.py


def read(ctx):
    durs = _trace.kernel_events(ctx.trace, KERNEL)
    if not durs:
        return None
    c, wl = ctx.cfg, ctx.wl
    rows = ctx.session.rows_per_worker
    R = wl["trials"] // len(ctx.trace.ops)          # realizations per call
    flops, nbytes = _counting.fused_gradient(c["m"], rows, c["p"], R)
    ideal = len(durs) * _counting.roofline_s(flops, nbytes, ctx.peaks)
    return 100.0 * ideal / sum(durs)
