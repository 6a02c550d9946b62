"""Model FLOPs of coded training of a DeepSeek-V2 share (MLA, DeepSeekMoE
with experts held here, leading dense layers), fixed whatever implements
it.  Counted from the configuration file's keys (``dsv2lite-coded-l5``).

A token passes through, in every layer, the MLA projections (W_q, W_kva,
W_kvb, W_o); in a dense layer the gated MLP of width d_ff; in an expert
layer the router (d x router experts), the shared experts (one gated MLP
of width n_shared * expert width) and, on average, k * held / E_router of
the held experts (the share of its k choices that fall here; tokens
dropped for capacity are not subtracted); and the tied head V x d.
Training counts 6 FLOPs per matrix parameter a token passes through
(forward 2, backward 4), plus causal attention: per token and layer
forward the scores over S/2 keys of the qk width and the values of the v
width, 2 * (S/2) * H * (qk + v), 3x for forward and backward.
Recomputation does not count."""
from __future__ import annotations


def mla_params(d: int, heads: int, qk_nope: int, qk_rope: int, v: int,
               kv_rank: int) -> int:
    """Matrix parameters of one MLA block (no query compression)."""
    return (d * heads * (qk_nope + qk_rope) + d * (kv_rank + qk_rope)
            + kv_rank * heads * (qk_nope + v) + heads * v * d)


def token_matmul_params(c: dict) -> float:
    """Matrix parameters one token passes through, on average, in the
    share of configuration ``c`` (the file's keys)."""
    d = c["hidden_size"]
    attn = mla_params(d, c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"],
                      c["kv_lora_rank"])
    lead = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - lead
    fe = c["moe_intermediate_size"]
    expert = 3 * d * fe
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / c[
        "router_experts"]
    moe_layer = (attn + d * c["router_experts"]
                 + c["n_shared_experts"] * expert + routed * expert)
    dense_layer = attn + 3 * d * c["intermediate_size"]
    return lead * dense_layer + n_moe * moe_layer + c["vocab_size"] * d


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward."""
    attn = (3.0 * c["num_hidden_layers"] * seq_len * c["num_attention_heads"]
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))
    return 6.0 * token_matmul_params(c) + attn
