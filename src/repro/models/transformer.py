"""Model driver: builds any assigned architecture from its ArchConfig.

Decoder-only, MoE, hybrid (attn+mamba), xLSTM, encoder-decoder (whisper) and
VLM (qwen2-vl) all share the same machinery:

  * parameters: descriptor trees (models.common) — one period of blocks,
    stacked over ``n_periods`` and scanned (DESIGN §5); leading dense
    layers (``first_dense_layers``, DeepSeek-V2) are a stack of their own,
    scanned before the periods;
  * attention blocks are GQA, or MLA where ``kv_lora_rank`` is set;
  * three execution paths: ``forward`` (full-seq, train), ``prefill``
    (full-seq + cache build), ``decode_step`` (one token + cache);
  * logits are tied to the token embedding.

Caches are per-period-position NamedTuples stacked over n_periods, matching
the scan layout.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, BlockSpec
from . import attention as attn
from . import mamba as mb
from . import mla
from . import xlstm as xl
from .common import (pdef, tree_init, tree_axes, stack_defs, rmsnorm,
                     layernorm, softcap, Dtype)
from .mlp import mlp_defs, mlp_apply
from .moe import moe_defs, moe_apply, moe_aux_zeros
from .rope import rope_angles, mrope_angles, apply_rope, sinusoidal_positions

__all__ = ["param_defs", "init_params", "param_axes", "forward", "prefill",
           "decode_step", "init_caches", "lm_loss", "Model"]


# ------------------------------------------------------------ param defs ---

def _norm_defs(cfg, name):
    d = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {name: pdef((d,), ("embed",), init="zeros")}
    return {name: pdef((d,), ("embed",), init="zeros"),
            name + "_b": pdef((d,), ("embed",), init="zeros")}


def _apply_norm(cfg, p, name, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p[name])
    return layernorm(x, p[name], p[name + "_b"])


def _block_defs(cfg, spec: BlockSpec):
    d = {}
    d.update(_norm_defs(cfg, "norm1"))
    if spec.kind == "attn":
        d.update(mla.mla_defs(cfg) if cfg.kv_lora_rank else attn.attn_defs(cfg))
        if spec.cross_attn:
            d.update(_norm_defs(cfg, "normc"))
            d.update(attn.attn_defs(cfg, cross=True))
    elif spec.kind == "mamba":
        d.update(mb.mamba_defs(cfg))
    elif spec.kind == "mlstm":
        d.update(xl.mlstm_defs(cfg))
    elif spec.kind == "slstm":
        d.update(xl.slstm_defs(cfg))
    else:
        raise ValueError(spec.kind)
    if spec.mlp:
        d.update(_norm_defs(cfg, "norm2"))
        d.update(moe_defs(cfg) if spec.moe else mlp_defs(cfg))
    if cfg.post_block_norm:
        d.update(_norm_defs(cfg, "postn1"))
        if spec.mlp:
            d.update(_norm_defs(cfg, "postn2"))
    return d


_LEAD = BlockSpec("attn")      # a leading dense layer


def _groups(cfg: ArchConfig):
    """(params key, block specs) of each scanned stack, in order: the
    leading dense layers (one spec, stacked over first_dense_layers), then
    the periods."""
    lead = [("lead", (_LEAD,))] if cfg.first_dense_layers else []
    return lead + [("blocks", cfg.period)]


def param_defs(cfg: ArchConfig):
    defs: dict = {
        "embed": pdef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                      scale=1.0),
        "blocks": {str(i): stack_defs(_block_defs(cfg, s), cfg.n_periods)
                   for i, s in enumerate(cfg.period)},
    }
    if cfg.first_dense_layers:
        defs["lead"] = {"0": stack_defs(_block_defs(cfg, _LEAD),
                                        cfg.first_dense_layers)}
    defs.update(_norm_defs(cfg, "final_norm"))
    if cfg.n_enc_layers:
        enc_spec = BlockSpec("attn")
        defs["encoder"] = {
            "blocks": stack_defs(_block_defs(cfg, enc_spec), cfg.n_enc_layers),
        }
        defs["encoder"].update(_norm_defs(cfg, "enc_norm"))
    if cfg.n_patches:
        defs["projector"] = pdef((cfg.d_vision, cfg.d_model),
                                 (None, "embed"))
    return defs


def init_params(cfg: ArchConfig, key: jax.Array):
    return tree_init(param_defs(cfg), key, Dtype.of(cfg.param_dtype))


def param_axes(cfg: ArchConfig):
    return tree_axes(param_defs(cfg))


# ------------------------------------------------------------- rope ctx ----

def _rope_ctx(cfg: ArchConfig, positions: jax.Array,
              mrope_positions: Optional[jax.Array]):
    """cos/sin for the given positions; positions: (S,) or scalar decode."""
    if cfg.mrope_sections is not None and mrope_positions is not None:
        return mrope_angles(mrope_positions, cfg.hd, cfg.mrope_sections,
                            cfg.rope_theta)                # (B, S, half)
    if cfg.learned_pos:  # whisper-style: additive sinusoidal, no rotary
        return None
    cos, sin = rope_angles(positions, cfg.rope_dim, cfg.rope_theta,
                           cfg.rope_yarn)                  # (S, half)
    return cos[None], sin[None]


def _make_rope_fn(ctx):
    if ctx is None:
        return lambda t, pos=None: t
    cos, sin = ctx
    return lambda t, pos=None: apply_rope(t, cos, sin)


# ----------------------------------------------------------- block apply ---

def _attn_full(bp, spec, x, cfg, rope_ctx, causal, want_cache, enc_out,
               cache_len=None):
    """Full-sequence attention sublayer. Returns (delta, cache|None)."""
    with (jax.named_scope("mla") if cfg.kv_lora_rank
          else contextlib.nullcontext()):
        return _attn_full_body(bp, spec, x, cfg, rope_ctx, causal,
                               want_cache, enc_out, cache_len)


def _attn_full_body(bp, spec, x, cfg, rope_ctx, causal, want_cache, enc_out,
                    cache_len):
    B, S, _ = x.shape
    rope_fn = _make_rope_fn(rope_ctx)
    scale = None
    if cfg.kv_lora_rank:
        q, k, v = mla.mla_qkv(bp, x, cfg, rope_fn)
        scale = mla.softmax_scale(cfg)
    else:
        q, k, v = attn.qkv_proj(bp, x)
        q, k = rope_fn(q), rope_fn(k)
    if cfg.seq_parallel_attn:
        # O2 (§Perf): when heads don't divide the model axis, shard the
        # QUERY SEQUENCE over `model` instead — attention compute stays
        # 256-way parallel for 24/28/12-head archs.
        from jax.sharding import PartitionSpec as P
        q = jax.lax.with_sharding_constraint(q, P(None, "model", None, None))
    pos = jnp.arange(S, dtype=jnp.int32)
    valid = jnp.ones((S,), bool)
    o = attn.attention(q, k, v, causal=causal, window=spec.window,
                       cap=cfg.attn_softcap, qpos=pos, kpos=pos, kvalid=valid,
                       chunk=cfg.attn_chunk, banded=cfg.banded_window,
                       scale=scale)
    if cfg.seq_parallel_attn:
        from jax.sharding import PartitionSpec as P
        o = jax.lax.with_sharding_constraint(o, P(None, "model", None, None))
    delta = attn.out_proj(bp, o)
    cache = None
    if want_cache:
        W = spec.window
        if W is not None and S > W:
            assert S % W == 0, "ring-buffer prefill needs S % window == 0"
            k, v = k[:, S - W:], v[:, S - W:]
        else:
            # Pre-allocate decode headroom (ring size capped at the window).
            target = cache_len if cache_len is not None else S
            if W is not None:
                target = min(target, W)
            if target > S:
                pad = ((0, 0), (0, target - S), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = attn.AttnCache(k, v)
    if spec.cross_attn:
        xc = _apply_norm(cfg, bp, "normc", x)
        qc, _, _ = attn.qkv_proj(bp, xc, pre="c")
        F = enc_out.shape[1]
        ck = jnp.einsum("bfd,dhk->bfhk", enc_out, bp["cwk"])
        cv = jnp.einsum("bfd,dhk->bfhk", enc_out, bp["cwv"])
        oc = attn.attention(qc, ck, cv, causal=False, window=None, cap=None,
                            qpos=pos, kpos=jnp.arange(F, dtype=jnp.int32),
                            kvalid=jnp.ones((F,), bool), chunk=cfg.attn_chunk)
        delta = delta + attn.out_proj(bp, oc, pre="c")
        if want_cache:
            cache = (cache, attn.AttnCache(ck, cv))
    return delta, cache


def _block_full(bp, spec: BlockSpec, x, cfg, rope_ctx, aux, *, causal=True,
                want_cache=False, enc_out=None, cache_len=None):
    """One block, full-sequence. Returns (x, cache, aux)."""
    if cfg.seq_parallel_mlp:
        from jax.sharding import PartitionSpec as P
        x = jax.lax.with_sharding_constraint(x, P(None, "model", None))
    h = _apply_norm(cfg, bp, "norm1", x)
    cache = None
    if spec.kind == "attn":
        delta, cache = _attn_full(bp, spec, h, cfg, rope_ctx, causal,
                                  want_cache, enc_out, cache_len=cache_len)
    elif spec.kind == "mamba":
        out = mb.mamba_apply(bp, h, cfg, return_cache=want_cache)
        delta, cache = out if want_cache else (out, None)
    elif spec.kind == "mlstm":
        out = xl.mlstm_apply(bp, h, cfg, return_cache=want_cache)
        delta, cache = out if want_cache else (out, None)
    elif spec.kind == "slstm":
        out = xl.slstm_apply(bp, h, cfg, return_cache=want_cache)
        delta, cache = out if want_cache else (out, None)
    if cfg.post_block_norm:
        delta = _apply_norm(cfg, bp, "postn1", delta)
    x = x + delta
    if spec.mlp:
        h2 = _apply_norm(cfg, bp, "norm2", x)
        if spec.moe:
            delta2, losses = moe_apply(bp, h2, cfg)
            aux = {k: aux.get(k, 0.0) + v for k, v in losses.items()}
        else:
            delta2 = mlp_apply(bp, h2, cfg)
        if cfg.post_block_norm:
            delta2 = _apply_norm(cfg, bp, "postn2", delta2)
        x = x + delta2
    return x, cache, aux


def _block_decode(bp, spec: BlockSpec, x, cfg, cache, index, rope_decode):
    """One block, single-token decode. Returns (x, new_cache)."""
    h = _apply_norm(cfg, bp, "norm1", x)
    if spec.kind == "attn":
        if spec.cross_attn:
            self_cache, cross_cache = cache
        else:
            self_cache = cache
        qkv = scale = None
        if cfg.kv_lora_rank:
            pos = jnp.asarray(index)[None]
            qkv = lambda t: mla.mla_qkv(bp, t, cfg,
                                        lambda r: rope_decode(r, pos))
            scale = mla.softmax_scale(cfg)
        delta, new_self = attn.decode_attend(
            bp, h, self_cache, index, cfg=cfg, window=spec.window,
            cap=cfg.attn_softcap, rope_fn=rope_decode, qkv=qkv, scale=scale)
        if spec.cross_attn:
            xc = _apply_norm(cfg, bp, "normc", x)
            qc = jnp.einsum("bsd,dhk->bshk", xc, bp["cwq"])
            F = cross_cache.k.shape[1]
            oc = attn.attention(
                qc, cross_cache.k, cross_cache.v, causal=False, window=None,
                cap=None, qpos=jnp.zeros((1,), jnp.int32),
                kpos=jnp.arange(F, dtype=jnp.int32),
                kvalid=jnp.ones((F,), bool), chunk=cfg.attn_chunk)
            delta = delta + attn.out_proj(bp, oc, pre="c")
            new_cache = (new_self, cross_cache)
        else:
            new_cache = new_self
    elif spec.kind == "mamba":
        delta, new_cache = mb.mamba_decode(bp, h, cache, cfg)
    elif spec.kind == "mlstm":
        delta, new_cache = xl.mlstm_decode(bp, h, cache, cfg)
    elif spec.kind == "slstm":
        delta, new_cache = xl.slstm_decode(bp, h, cache, cfg)
    if cfg.post_block_norm:
        delta = _apply_norm(cfg, bp, "postn1", delta)
    x = x + delta
    if spec.mlp:
        h2 = _apply_norm(cfg, bp, "norm2", x)
        if spec.moe:
            delta2, _ = moe_apply(bp, h2, cfg)
        else:
            delta2 = mlp_apply(bp, h2, cfg)
        if cfg.post_block_norm:
            delta2 = _apply_norm(cfg, bp, "postn2", delta2)
        x = x + delta2
    return x, new_cache


# ------------------------------------------------------------ remat glue ---

def _maybe_remat(cfg, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# -------------------------------------------------------------- encoder ----

def _encode(params, cfg: ArchConfig, enc_embeds):
    """Whisper-style encoder over stub frame embeddings (B, F, d)."""
    ep = params["encoder"]
    B, F, _ = enc_embeds.shape
    x = enc_embeds + sinusoidal_positions(
        jnp.arange(F), cfg.d_model)[None].astype(enc_embeds.dtype)
    spec = BlockSpec("attn")

    def body(x, bp):
        x, _, _ = _block_full(bp, spec, x, cfg, None, {},
                              causal=cfg.causal_encoder, want_cache=False)
        return x, None

    x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, ep["blocks"])
    return _apply_norm(cfg, ep, "enc_norm", x)


# ---------------------------------------------------------- embed/logits ---

def _embed_inputs(params, cfg: ArchConfig, tokens, patch_embeds,
                  positions=None):
    dt = Dtype.of(cfg.dtype)
    x = params["embed"][tokens].astype(dt)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.n_patches and patch_embeds is not None:
        proj = jnp.einsum("bnv,vd->bnd", patch_embeds.astype(dt),
                          params["projector"].astype(dt))
        # patches occupy the first n_patches positions of the stream
        x = jnp.concatenate([proj, x[:, cfg.n_patches:]], axis=1)
    if cfg.learned_pos:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        x = x + sinusoidal_positions(positions, cfg.d_model)[None].astype(dt)
    return x


def _logits(params, cfg: ArchConfig, x):
    x = _apply_norm(cfg, params, "final_norm", x)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    return softcap(logits, cfg.final_softcap)


# ------------------------------------------------------------ main paths ---

def forward(params, cfg: ArchConfig, tokens, *, patch_embeds=None,
            mrope_positions=None, enc_embeds=None):
    """Full-sequence forward -> (logits (B, S, V) f32, aux dict)."""
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    S = x.shape[1]
    rope_ctx = _rope_ctx(cfg, jnp.arange(S, dtype=jnp.int32), mrope_positions)
    enc_out = _encode(params, cfg, enc_embeds) if cfg.n_enc_layers else None

    def group_body(specs):
        def body(carry, bps):
            x, aux = carry
            for i, spec in enumerate(specs):
                x, _, aux = _block_full(bps[str(i)], spec, x, cfg, rope_ctx,
                                        aux, want_cache=False,
                                        enc_out=enc_out)
            return (x, aux), None
        return body

    aux = {"load_balance": jnp.zeros((), jnp.float32),
           "router_z": jnp.zeros((), jnp.float32)}
    if any(b.moe for b in cfg.period):
        aux = moe_aux_zeros()
    for key, specs in _groups(cfg):
        (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, group_body(specs)),
                                   (x, aux), params[key])
    return _logits(params, cfg, x), aux


def prefill(params, cfg: ArchConfig, tokens, *, patch_embeds=None,
            mrope_positions=None, enc_embeds=None, cache_len=None):
    """Full-sequence forward building caches -> (last-pos logits, caches).

    ``cache_len`` > S pre-allocates decode headroom in non-windowed caches.
    """
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    S = x.shape[1]
    rope_ctx = _rope_ctx(cfg, jnp.arange(S, dtype=jnp.int32), mrope_positions)
    enc_out = _encode(params, cfg, enc_embeds) if cfg.n_enc_layers else None

    def group_body(specs):
        def body(x, bps):
            caches = []
            for i, spec in enumerate(specs):
                x, cache, _ = _block_full(bps[str(i)], spec, x, cfg, rope_ctx,
                                          {}, want_cache=True,
                                          enc_out=enc_out,
                                          cache_len=cache_len)
                caches.append(cache)
            return x, tuple(caches)
        return body

    caches = []
    for key, specs in _groups(cfg):
        x, c = jax.lax.scan(group_body(specs), x, params[key])
        caches.append(c)
    logits = _logits(params, cfg, x[:, -1:])
    return logits, _pack_caches(caches)


def decode_step(params, cfg: ArchConfig, token, caches, index, *,
                mrope_positions=None):
    """One decode step. token: (B, 1) int32; index: scalar current position.

    Returns (logits (B, 1, V), new caches).
    """
    x = _embed_inputs(params, cfg, token, None,
                      positions=jnp.asarray(index)[None])

    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(jnp.asarray(index, jnp.int32),
                                (3, token.shape[0], 1))
        rope_ctx = mrope_angles(pos3, cfg.hd, cfg.mrope_sections,
                                cfg.rope_theta)
        rope_decode = _make_rope_fn(rope_ctx)
    elif cfg.learned_pos:
        rope_decode = lambda t, pos=None: t
    else:
        def rope_decode(t, pos):
            cos, sin = rope_angles(pos, cfg.rope_dim, cfg.rope_theta,
                                   cfg.rope_yarn)
            return apply_rope(t, cos[None], sin[None])

    def group_body(specs):
        def body(x, xs):
            bps, caches_p = xs
            new = []
            for i, spec in enumerate(specs):
                x, nc = _block_decode(bps[str(i)], spec, x, cfg, caches_p[i],
                                      index, rope_decode)
                new.append(nc)
            return x, tuple(new)
        return body

    groups = _groups(cfg)
    caches = caches if len(groups) > 1 else (caches,)
    new_caches = []
    for (key, specs), c in zip(groups, caches):
        x, nc = jax.lax.scan(group_body(specs), x, (params[key], c))
        new_caches.append(nc)
    return _logits(params, cfg, x), _pack_caches(new_caches)


def _pack_caches(caches: list):
    """Per-group caches as the model hands them out: the periods' tuple
    alone, or (leading layers', periods') where leading layers exist."""
    return caches[0] if len(caches) == 1 else tuple(caches)


def init_caches(cfg: ArchConfig, B: int, cache_len: int):
    """Zero caches matching prefill's structure (stacked over n_periods;
    a pair (leading layers, periods) where the arch has leading layers)."""
    groups = []
    for key, specs in _groups(cfg):
        n = cfg.first_dense_layers if key == "lead" else cfg.n_periods
        groups.append(_init_group_caches(cfg, specs, n, B, cache_len))
    return _pack_caches(groups)


def _init_group_caches(cfg: ArchConfig, specs, n: int, B: int,
                       cache_len: int):
    dt = Dtype.of(cfg.dtype)
    per_pos = []
    for spec in specs:
        if spec.kind == "attn":
            C = min(cache_len, spec.window) if spec.window else cache_len
            if cfg.kv_lora_rank:
                c = attn.init_kv_cache(
                    B, C, cfg.n_heads,
                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, dt,
                    hv=cfg.v_head_dim)
            else:
                c = attn.init_kv_cache(B, C, cfg.n_kv, cfg.hd, dt)
            if spec.cross_attn:
                c = (c, attn.init_kv_cache(B, max(cfg.n_enc_frames, 1),
                                           cfg.n_kv, cfg.hd, dt))
        elif spec.kind == "mamba":
            c = mb.init_mamba_cache(cfg, B, dt)
        elif spec.kind == "mlstm":
            c = xl.init_mlstm_cache(cfg, B, dt)
        elif spec.kind == "slstm":
            c = xl.init_slstm_cache(cfg, B, dt)
        per_pos.append(c)
    stack = lambda t: jnp.broadcast_to(t[None], (n,) + t.shape)
    return jax.tree.map(stack, tuple(per_pos))


# ---------------------------------------------------------- param counts ---

def count_params(cfg: ArchConfig, active_only: bool = False) -> float:
    """Total (or MoE-active) parameter count from the descriptor tree.

    active_only scales expert weights by top_k / n_experts — the N used in
    MODEL_FLOPS = 6 N D for MoE (§Roofline).
    """
    import math as _math

    total = 0.0

    def walk(d):
        nonlocal total
        if isinstance(d, dict) and d.get("__pdef__") is True:
            return
        for k, v in d.items():
            if k == "__pdef__":
                continue
            if isinstance(v, dict) and v.get("__pdef__") is True:
                n = float(_math.prod(v["shape"]))
                if active_only and k.startswith("moe_w") and cfg.n_experts:
                    n *= cfg.top_k / cfg.n_experts
                total += n
            else:
                walk(v)

    walk(param_defs(cfg))
    return total


# ------------------------------------------------------------------ loss ---

def lm_loss(logits, labels, weights=None):
    """Weighted next-token cross entropy. logits: (B,S,V) f32; labels (B,S)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if weights is None:
        weights = jnp.ones_like(ll)
    return -(ll * weights).sum() / jnp.maximum(weights.sum(), 1.0)


@dataclasses.dataclass(frozen=True)
class Model:
    """Convenience bundle of the functional API for one architecture."""
    cfg: ArchConfig

    def init(self, key):
        return init_params(self.cfg, key)

    def axes(self):
        return param_axes(self.cfg)

    forward = staticmethod(forward)

    def __call__(self, params, tokens, **kw):
        return forward(params, self.cfg, tokens, **kw)

    def prefill(self, params, tokens, **kw):
        return prefill(params, self.cfg, tokens, **kw)

    def decode(self, params, token, caches, index, **kw):
        return decode_step(params, self.cfg, token, caches, index, **kw)
