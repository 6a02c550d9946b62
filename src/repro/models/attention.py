"""GQA attention with chunked online-softmax, sliding windows, soft-capping,
ring-buffer KV caches, and cross-attention — all pure JAX (jnp/lax).

Memory-efficient attention: KV is processed in chunks of ``cfg.attn_chunk``
with a running (max, denom, acc) carry — the flash-attention recurrence —
so prefill at 32k/524k never materializes an (Sq, Skv) score matrix bigger
than (Sq, chunk).  This is also what keeps the dry-run's HLO temp memory
honest (DESIGN §5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .common import pdef, softcap

__all__ = ["attn_defs", "qkv_proj", "out_proj", "attention", "init_kv_cache",
           "ring_slot_positions", "decode_attend", "AttnCache"]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def attn_defs(cfg, cross: bool = False):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    pre = "c" if cross else ""
    return {
        pre + "wq": pdef((d, H, hd), ("embed", "heads", "head_dim"), fan_in=d),
        pre + "wk": pdef((d, K, hd), ("embed", "kv", "head_dim"), fan_in=d),
        pre + "wv": pdef((d, K, hd), ("embed", "kv", "head_dim"), fan_in=d),
        pre + "wo": pdef((H, hd, d), ("heads", "head_dim", "embed"),
                         fan_in=H * hd),
    }


def qkv_proj(p, x, pre: str = ""):
    """x: (B, S, d) -> q (B,S,H,hd), k (B,S,K,hd), v (B,S,K,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p[pre + "wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p[pre + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p[pre + "wv"])
    return q, k, v


def out_proj(p, o, pre: str = ""):
    return jnp.einsum("bshk,hkd->bsd", o, p[pre + "wo"])


def _mask(qpos, kpos, kvalid, causal: bool, window: Optional[int]):
    """(Sq, Skv) boolean mask from integer positions."""
    m = jnp.broadcast_to(kvalid[None, :], (qpos.shape[0], kpos.shape[0]))
    if causal:
        m = m & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    return m


def _scores(q, k, scale, cap):
    """q: (B,K,G,Sq,hd), k: (B,C,K,hd) -> (B,K,G,Sq,C) float32."""
    s = jnp.einsum("bkgsh,bckh->bkgsc", q, k,
                   preferred_element_type=jnp.float32) * scale
    return softcap(s, cap)


def attention(q, k, v, *, causal: bool, window: Optional[int],
              cap: Optional[float], qpos, kpos, kvalid,
              chunk: int = 1024, banded: bool = False,
              scale: Optional[float] = None) -> jax.Array:
    """Online-softmax GQA attention.

    q, k: (B, Sq|Skv, H|K, hd);  v: (B, Skv, K, hv) (hv may differ from
    hd, as in MLA);  qpos: (Sq,) int32;  kpos, kvalid: (Skv,).  ``scale``
    defaults to hd ** -0.5.  Returns (B, Sq, H, hv) in q.dtype.
    """
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    hv = v.shape[-1]
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    qh = q.reshape(B, Sq, K, G, hd).transpose(0, 2, 3, 1, 4)  # (B,K,G,Sq,hd)

    if Skv <= chunk or Skv % chunk:
        s = _scores(qh, k, scale, cap)
        m = _mask(qpos, kpos, kvalid, causal, window)
        s = jnp.where(m[None, None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgsc,bckh->bkgsh", p, v.astype(jnp.float32))
        return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hv).astype(q.dtype)

    if (banded and window is not None and causal and Sq == Skv
            and Skv >= 4 * window and window % chunk == 0):
        return _banded_attention(qh, k, v, window=window, cap=cap,
                                 scale=scale, chunk=chunk, qpos=qpos,
                                 out_dtype=q.dtype)

    n_chunks = Skv // chunk
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hv).transpose(1, 0, 2, 3, 4)
    kposc = kpos.reshape(n_chunks, chunk)
    kvalc = kvalid.reshape(n_chunks, chunk)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, kp, kv_ok = xs
        s = _scores(qh, kb, scale, cap)                    # (B,K,G,Sq,C)
        msk = _mask(qpos, kp, kv_ok, causal, window)
        s = jnp.where(msk[None, None, None], s, _NEG)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        r = jnp.exp(m_run - m_new)
        # Explicitly zero masked entries: when a whole chunk is masked,
        # s - m_new == 0 would otherwise give weight exp(0) = 1.
        p = jnp.exp(s - m_new[..., None]) * msk[None, None, None]
        l_new = l_run * r + p.sum(axis=-1)
        acc = acc * r[..., None] + jnp.einsum(
            "bkgsc,bckh->bkgsh", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((B, K, G, Sq), _NEG, jnp.float32),
            jnp.zeros((B, K, G, Sq), jnp.float32),
            jnp.zeros((B, K, G, Sq, hv), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(body, init, (kc, vc, kposc, kvalc))
    o = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hv).astype(q.dtype)


def _banded_attention(qh, k, v, *, window, cap, scale, chunk, qpos,
                      out_dtype):
    """Sliding-window self-attention without the O(S^2) masked waste.

    q blocks of size ``chunk`` only visit the ``window/chunk + 1`` KV blocks
    that can fall inside the window — compute drops from S*S to
    S*(window+chunk) (§Perf optimization O1, beyond-paper).

    qh: (B, K, G, S, hd) grouped queries; k, v: (B, S, K, hd).
    """
    B, K, G, S, hd = qh.shape
    nq = S // chunk
    nb = window // chunk + 1                     # KV blocks per q block
    qb = qh.reshape(B, K, G, nq, chunk, hd)
    hv = v.shape[-1]
    kb = k.reshape(B, nq, chunk, K, hd)
    vb = v.reshape(B, nq, chunk, K, hv)
    # for q block i, kv blocks i-nb+1 .. i (clamped; out-of-range masked)
    offs = jnp.arange(nq)[:, None] - jnp.arange(nb - 1, -1, -1)[None, :]
    valid_blk = offs >= 0
    gather = jnp.clip(offs, 0, nq - 1)                   # (nq, nb)
    kg = kb[:, gather]                                   # (B, nq, nb, C, K, hd)
    vg = vb[:, gather]
    s = jnp.einsum("bkgiqh,binckh->bkgiqnc", qb, kg,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)                                  # (B,K,G,nq,Cq,nb,Ckv)
    qp = qpos.reshape(nq, chunk)[:, :, None, None]       # (nq, Cq, 1, 1)
    kp = (gather[:, :, None] * chunk
          + jnp.arange(chunk)[None, None, :])            # (nq, nb, Ckv)
    kp = kp[:, None, :, :]                               # (nq, 1, nb, Ckv)
    msk = ((kp <= qp) & (kp > qp - window)
           & valid_blk[:, None, :, None])                # (nq, Cq, nb, Ckv)
    s = jnp.where(msk[None, None, None], s, _NEG)
    sh = s.shape
    p = jax.nn.softmax(s.reshape(sh[:-2] + (nb * chunk,)),
                       axis=-1).reshape(sh)
    o = jnp.einsum("bkgiqnc,binckh->bkgiqh", p, vg.astype(jnp.float32))
    o = o.reshape(B, K, G, S, hv).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, S, K * G, hv).astype(out_dtype)


class AttnCache(NamedTuple):
    """KV cache for one attention layer (ring buffer when windowed)."""
    k: jax.Array   # (B, C, K, hd)
    v: jax.Array   # (B, C, K, hv)


def init_kv_cache(B: int, cache_len: int, K: int, hd: int,
                  dtype, hv: Optional[int] = None) -> AttnCache:
    return AttnCache(jnp.zeros((B, cache_len, K, hd), dtype),
                     jnp.zeros((B, cache_len, K, hv or hd), dtype))


def ring_slot_positions(cache_len: int, index) -> tuple[jax.Array, jax.Array]:
    """Positions and validity of ring-buffer slots given current length.

    Slot s holds the largest position p < index with p ≡ s (mod cache_len);
    valid iff p >= 0.  For a non-ring (full) cache this reduces to
    pos = s, valid = s < index.
    """
    s = jnp.arange(cache_len, dtype=jnp.int32)
    idx = jnp.asarray(index, jnp.int32)
    p = idx - 1 - jnp.mod(idx - 1 - s, cache_len)
    return p, p >= 0


def decode_attend(p, x, cache: AttnCache, index, *, cfg, window, cap,
                  rope_fn, pre: str = "", qkv=None,
                  scale: Optional[float] = None) -> tuple[jax.Array, AttnCache]:
    """Single-token decode: write (k, v) at slot index % C, attend over cache.

    x: (B, 1, d); index: scalar int32 current position. rope_fn(q_or_k, pos)
    applies rotary for this arch (identity for non-rope archs).  ``qkv``,
    where given, maps x to already rotated (q, k, v) in place of the GQA
    projections (MLA).
    """
    if qkv is None:
        q, k_new, v_new = qkv_proj(p, x, pre)
        q = rope_fn(q, jnp.asarray(index)[None])
        k_new = rope_fn(k_new, jnp.asarray(index)[None])
    else:
        q, k_new, v_new = qkv(x)
    C = cache.k.shape[1]
    slot = jnp.mod(jnp.asarray(index, jnp.int32), C)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), slot, axis=1)
    kpos, kvalid = ring_slot_positions(C, index + 1)
    o = attention(q, k, v, causal=True, window=window, cap=cap,
                  qpos=jnp.asarray(index, jnp.int32)[None], kpos=kpos,
                  kvalid=kvalid, chunk=cfg.attn_chunk, scale=scale)
    return out_proj(p, o, pre), AttnCache(k, v)
