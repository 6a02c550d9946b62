"""Load of the experts held here: a row's assignments to its most loaded
held expert over the held experts' mean, from the trainer's counters
``moe_peak`` and ``moe_routed`` (``train.coded.CodedTrainer.metrics``,
each summed over rows, workers, layers and the window's steps):
moe_peak / (moe_routed / experts held).  1 is an even load over the
held experts.  An expert's capacity is the whole layer's mean load,
int(S k / E) assignments a row, and what a held expert takes past it drops
(``dropped_share.moe``)."""


def read(ctx):
    routed = ctx.record.counts.get("moe_routed", 0)
    peak = ctx.record.counts.get("moe_peak", 0)
    if not routed or not peak:
        return None
    return peak * ctx.cfg["n_routed_experts"] / routed
