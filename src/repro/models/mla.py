"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Keys and values come from one compressed latent per token: ``W_kva h``
gives the latent c_kv (rank ``kv_lora_rank``, RMS-normalized) and a
rotary key of ``qk_rope_head_dim`` dims that every head shares; ``W_kvb``
expands c_kv into each head's unrotated key and its value.  Queries are
``W_q h`` (no query compression), split into unrotated and rotary parts.
A head's key is [k_nope, k_rope] (qk dims = nope + rope) and its value has
``v_head_dim`` dims, so q·k and the values differ in width.

Training computes the naive (up-projected) form; absorbing ``W_kvb`` into
the query and output projections is the same mathematics.  The softmax
scale is (nope + rope) ** -0.5 times YaRN's mscale(factor,
mscale_all_dim) squared.
"""
from __future__ import annotations

import jax.numpy as jnp

from .common import pdef, rmsnorm
from .rope import yarn_mscale

__all__ = ["mla_defs", "mla_qkv", "softmax_scale"]


def mla_defs(cfg):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": pdef((d, H, dn + dr), ("embed", "heads", "head_dim"), fan_in=d),
        "wkv_a": pdef((d, r + dr), ("embed", None), fan_in=d),
        "kv_norm": pdef((r,), (None,), init="zeros"),
        "wkv_b": pdef((r, H, dn + dv), (None, "heads", "head_dim"),
                      fan_in=r),
        "wo": pdef((H, dv, d), ("heads", "head_dim", "embed"),
                   fan_in=H * dv),
    }


def mla_qkv(p, x, cfg, rope_fn):
    """x: (B, S, d) -> q, k (B, S, H, nope + rope), v (B, S, H, v_head_dim);
    ``rope_fn`` rotates a (B, S, heads, rope) array."""
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kv_a = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    c_kv = rmsnorm(kv_a[..., :r], p["kv_norm"])
    k_rope = rope_fn(kv_a[..., None, r:])                  # (B, S, 1, rope)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], rope_fn(q[..., dn:])], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                  + k_rope.shape[-1:])], axis=-1)
    return q, k, v


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s
