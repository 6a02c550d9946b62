"""Model FLOP utilization of coded training of the DeepSeek-V2 share on
four chips: the model FLOPs of the distinct tokens trained in the traced
window (``_counting_moe.train_flops_per_token``: the MLA, dense, shared
and the expected share of routed expert parameters a token passes through,
and causal attention at the cell's sequence length) over the window's
seconds and the bf16 peak of all the cell's chips.  The beta-fold
redundant computation of the code is not counted, so this cannot pass
100 / beta percent."""
from chipbench.metrics import _counting_moe


def read(ctx):
    tokens = ctx.record.counts.get("tokens", 0)
    if not tokens or ctx.window_s <= 0 or not ctx.trace.ops:
        return None
    per_token = _counting_moe.train_flops_per_token(ctx.cfg,
                                                    ctx.wl["seq_len"])
    chips = len(ctx.session.devices)
    return 100.0 * per_token * tokens / ctx.window_s / (
        chips * ctx.peaks["flops_per_s"])
