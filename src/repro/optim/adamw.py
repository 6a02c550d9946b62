"""AdamW with dtype-configurable moments (bf16 for the >=100B configs) and
global-norm gradient clipping.  Pure functional, pytree-shaped like params,
so optimizer state inherits the parameter shardings under pjit.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWState", "adamw_init", "adamw_update", "cosine_schedule",
           "global_norm"]


class AdamWState(NamedTuple):
    m: dict
    v: dict
    count: jax.Array


def adamw_init(params, dtype=jnp.float32) -> AdamWState:
    zeros = lambda t: jax.tree.map(
        lambda x: jnp.zeros_like(x, dtype=dtype), t)
    return AdamWState(zeros(params), zeros(params),
                      jnp.zeros((), jnp.int32))


def global_norm(tree, axis_name=None) -> jax.Array:
    """L2 norm of every leaf together; with ``axis_name`` (inside
    ``shard_map``) the leaves are each device's part of the tree, and the
    squares are summed over the axis."""
    sq = sum(jnp.vdot(x.astype(jnp.float32), x.astype(jnp.float32))
             for x in jax.tree.leaves(tree))
    if axis_name is not None:
        sq = jax.lax.psum(sq, axis_name)
    return jnp.sqrt(sq)


def adamw_update(grads, state: AdamWState, params, *, lr, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1, clip_norm=1.0, axis_name=None):
    """Returns (new_params, new_state, metrics).  ``axis_name``: the
    arguments are each device's part of the parameters, split over that
    ``shard_map`` axis; the clipping norm is taken over the whole."""
    gnorm = global_norm(grads, axis_name)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
    count = state.count + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        step = lr * (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
        step = step + lr * weight_decay * p.astype(jnp.float32)
        return ((p.astype(jnp.float32) - step).astype(p.dtype),
                m_new.astype(m.dtype), v_new.astype(v.dtype))

    out = jax.tree.map(upd, grads, state.m, state.v, params)
    new_params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdamWState(new_m, new_v, count), {"grad_norm": gnorm}


def cosine_schedule(base_lr: float, warmup: int, total: int):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * (step + 1.0) / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, cos)
    return lr
