"""Share of the window's assignments to the experts held here that the
expert layers dropped for capacity: the trainer's counters
``moe_dropped`` over ``moe_routed`` (``train.coded.CodedTrainer.metrics``,
summed over workers, layers and steps), taken over the window's steps."""


def read(ctx):
    routed = ctx.record.counts.get("moe_routed", 0)
    if not routed:
        return None
    return 100.0 * ctx.record.counts.get("moe_dropped", 0) / routed
