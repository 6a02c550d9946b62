"""Mixture-of-Experts layer: top-k token-choice routing with sort-based
capacity dispatch (no one-hot dispatch matmuls — gather/scatter only, so
compiled FLOPs track ACTIVE parameters, which matters for the §Roofline
'useful compute' ratio).

Dispatch is vmapped over the batch dim: each batch row sorts its own S*k
assignments, so under data-parallel sharding the sort stays device-local and
the only cross-device traffic is the (B, E, C, d) expert all-to-all that XLA
inserts when experts are sharded over the ``model`` axis (DESIGN §5).

Two router styles share the dispatch:

* Mixtral-style (default): top-k weights renormalized to sum to 1,
  Switch load-balance + router z-loss.
* DeepSeekMoE (arXiv:2405.04434 §2.2): softmax weights over all experts,
  not renormalized (``moe_renormalize=False``), ``n_shared_experts``
  always-on experts as one MLP of width n_shared * moe_d_ff, and the
  expert-level balance loss per sequence (``moe_balance="expert"``):
  sum_i f_i P_i with f_i = E / (k S) * #(tokens choosing i) and P_i the
  mean router probability of expert i.

An expert share: a layer may hold experts [expert_offset, expert_offset +
experts_held) of ``n_experts``.  It routes over all of them with the
router's full width and the whole layer's per-expert capacity
``int(capacity_factor * S * k / n_experts)``, and computes only its own
experts' part; assignments to experts held elsewhere add nothing here.
The shares of one layer therefore add up to the whole layer (with the
shared experts counted once).  The layer counts the assignments to its
experts (``moe_routed``), those past capacity (``moe_dropped``) and, summed
over rows, the assignments to its most loaded expert (``moe_peak``).

Aux losses are returned for logging and added to the training objective
with cfg.router_aux_weight.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import pdef, act_fn

__all__ = ["moe_defs", "moe_apply", "moe_aux_zeros"]


def moe_defs(cfg):
    d, E = cfg.d_model, cfg.experts_held or cfg.n_experts
    f = cfg.moe_d_ff or cfg.d_ff
    defs = {
        "router": pdef((d, cfg.n_experts), ("embed", None), scale=0.02),
        "moe_wi": pdef((E, d, f), ("expert", "embed", "ff"), fan_in=d),
        "moe_wg": pdef((E, d, f), ("expert", "embed", "ff"), fan_in=d),
        "moe_wo": pdef((E, f, d), ("expert", "ff", "embed"), fan_in=f),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        defs.update({
            "shared_wi": pdef((d, fs), ("embed", "ff")),
            "shared_wg": pdef((d, fs), ("embed", "ff")),
            "shared_wo": pdef((fs, d), ("ff", "embed")),
        })
    return defs


def moe_aux_zeros() -> dict:
    """The aux dict every MoE layer adds to: losses and the counters."""
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32),
            "moe_routed": jnp.zeros((), jnp.int32),
            "moe_dropped": jnp.zeros((), jnp.int32),
            "moe_peak": jnp.zeros((), jnp.int32)}


def _slots(expert_ids, weights, E: int, lo: int, held: int, C: int):
    """Per-batch-row slot table. expert_ids/weights: (S, k).

    Assignments are ranked within their expert in token order (argsort
    grouping); those to held experts with rank < C fill slot
    (expert - lo) * C + rank.  Returns (token (held*C,) int32 — S marks an
    empty slot, weight (held*C,), routed, dropped, the most assignments to
    one held expert)."""
    S, k = expert_ids.shape
    flat_e = expert_ids.reshape(-1)                     # (S*k,)
    order = jnp.argsort(flat_e, stable=True)            # group by expert
    e_sorted = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)             # (E,)
    offsets = jnp.cumsum(counts) - counts               # exclusive prefix
    rank = jnp.arange(S * k) - offsets[e_sorted]
    local = e_sorted - lo
    here = (local >= 0) & (local < held)
    keep = here & (rank < C)                            # capacity drop
    slot = jnp.where(keep, local * C + rank, held * C)  # held*C: discarded
    tok = jnp.full((held * C + 1,), S, jnp.int32).at[slot].set(
        (order // k).astype(jnp.int32))
    w = jnp.zeros((held * C + 1,), weights.dtype).at[slot].set(
        weights.reshape(-1)[order])
    return (tok[:-1], w[:-1], here.sum(dtype=jnp.int32),
            (here & ~keep).sum(dtype=jnp.int32),
            counts[lo:lo + held].max())


def _experts(p, buf, act):
    """buf: (B, E, C, d) -> (B, E, C, d) through each expert's gated MLP."""
    h = act(jnp.einsum("becd,edf->becf", buf, p["moe_wg"])) * jnp.einsum(
        "becd,edf->becf", buf, p["moe_wi"])
    return jnp.einsum("becf,efd->becd", h, p["moe_wo"])


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux dict: losses and counters)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    held, lo = cfg.experts_held or E, cfg.expert_offset
    C = max(int(cfg.capacity_factor * S * k / E), 1)

    with jax.named_scope("moe:route"):
        logits = jnp.einsum("bsd,de->bse", x, p["router"],
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)          # (B, S, k)
        if cfg.moe_renormalize:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        if cfg.moe_balance == "expert":
            # per sequence, then the mean over sequences
            f = jax.nn.one_hot(top_e, E).sum((1, 2)) * (E / (k * S))
            aux_lb = jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), -1))
            z_loss = jnp.zeros((), jnp.float32)
        else:
            # Switch: expert fractions of the first choice x router probs
            me = jnp.mean(probs, axis=(0, 1))           # (E,)
            ce = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=(0, 1))
            aux_lb = E * jnp.sum(me * ce)
            z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        tok, w, routed, dropped, peak = jax.vmap(
            lambda eb, wb: _slots(eb, wb, E, lo, held, C))(
                top_e, top_w.astype(x.dtype))           # tok, w: (B, held*C)

    with jax.named_scope("moe:experts"):
        xpad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
        buf = jax.vmap(lambda xb, tb: xb[tb])(xpad, tok)
        buf = buf.reshape(B, held, C, d)
        if getattr(cfg, "moe_local_dispatch", False):
            # §Perf B5: keep the data-dependent gather/scatter local to the
            # batch shard; only the expert einsum below moves data (one
            # clean all-to-all) instead of SPMD permute-chains.
            from jax.sharding import PartitionSpec as P
            buf = jax.lax.with_sharding_constraint(
                buf, P(("data",), None, None, None))
        act = act_fn(cfg.act)
        y = _experts(p, buf, act).reshape(B, held * C, d) * w[..., None]
        out = jax.vmap(lambda yb, tb: jnp.zeros((S + 1, d), y.dtype)
                       .at[tb].add(yb)[:S])(y, tok)
        if cfg.n_shared_experts:
            h = act(jnp.einsum("bsd,df->bsf", x, p["shared_wg"])) * jnp.einsum(
                "bsd,df->bsf", x, p["shared_wi"])
            out = out + jnp.einsum("bsf,fd->bsd", h, p["shared_wo"])
    return out, {"load_balance": aux_lb, "router_z": z_loss,
                 "moe_routed": routed.sum(), "moe_dropped": dropped.sum(),
                 "moe_peak": peak.sum(dtype=jnp.int32)}
