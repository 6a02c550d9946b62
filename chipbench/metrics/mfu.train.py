"""Model FLOP utilization of coded training: the model FLOPs of the
distinct tokens trained in the traced window
(``_counting.lm_train_flops_per_token``: 6 x the matrix parameters a token
passes through, plus causal attention at the cell's sequence length) over
the window's seconds and the chip's peak bf16 FLOP/s.  The beta-fold
redundant computation of the code is not counted, so this cannot pass
100 / beta percent."""
from chipbench.metrics import _counting


def read(ctx):
    tokens = ctx.record.counts.get("tokens", 0)
    if not tokens or ctx.window_s <= 0:
        return None
    c = ctx.cfg
    per_token = _counting.lm_train_flops_per_token(
        c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
        c["head_dim"], c["intermediate_size"], c["vocab_size"],
        ctx.wl["seq_len"])
    return 100.0 * per_token * tokens / ctx.window_s / ctx.peaks[
        "flops_per_s"]
