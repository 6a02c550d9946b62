"""MoE dispatch unit tests + properties."""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS
from repro.models.common import tree_init
from repro.models.moe import moe_apply, moe_defs

CFG = ARCHS["phi3.5-moe-42b-a6.6b"].smoke_variant()


def _setup(capacity_factor=4.0, seed=0):
    cfg = CFG.with_overrides(capacity_factor=capacity_factor)
    p = tree_init(moe_defs(cfg), jax.random.key(seed))
    return cfg, p


def test_dense_equivalence_at_full_capacity():
    """With capacity >= S*k, sort-based dispatch must equal the naive
    per-token expert mixture."""
    cfg, p = _setup(capacity_factor=8.0)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5
    out, _ = moe_apply(p, x, cfg)

    # naive reference
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    from repro.models.common import act_fn
    act = act_fn(cfg.act)

    def expert(e, xb):
        h = act(xb @ p["moe_wg"][e]) * (xb @ p["moe_wi"][e])
        return h @ p["moe_wo"][e]

    ref = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        w_e = jnp.where(top_e == e, top_w, 0.0).sum(-1)   # (B,S)
        ref = ref + w_e[..., None] * expert(e, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-4)


def test_capacity_drop_monotone():
    """Lower capacity can only drop tokens (output is damped, not corrupted)."""
    cfg_hi, p = _setup(capacity_factor=8.0)
    cfg_lo = cfg_hi.with_overrides(capacity_factor=0.5)
    x = jax.random.normal(jax.random.key(2), (1, 32, cfg_hi.d_model))
    hi, _ = moe_apply(p, x, cfg_hi)
    lo, _ = moe_apply(p, x, cfg_lo)
    assert float(jnp.abs(lo).sum()) <= float(jnp.abs(hi).sum()) + 1e-3


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 99))
def test_aux_losses_sane(seed):
    cfg, p = _setup(seed=seed)
    x = jax.random.normal(jax.random.key(seed), (2, 16, cfg.d_model))
    _, aux = moe_apply(p, x, cfg)
    lb = float(aux["load_balance"])
    assert 0.9 <= lb <= cfg.n_experts + 1e-3   # =1 when perfectly balanced
    assert float(aux["router_z"]) >= 0.0


def test_single_token_routing():
    cfg, p = _setup()
    x = jax.random.normal(jax.random.key(3), (4, 1, cfg.d_model))
    out, _ = moe_apply(p, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
