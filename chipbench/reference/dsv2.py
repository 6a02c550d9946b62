"""Plain reference of coded training of DeepSeek-V2-Lite's layers.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434;
the model's ``config.json``) in float32, importing nothing of the program:

* multi-head latent attention (§2.1) in its naive, up-projected form:
  q = W_q h split into unrotated and rotary parts; [c_kv, k_rope] =
  W_kva h; c_kv RMS-normalized; [k_nope, v] = W_kvb c_kv per head; the
  rotary key shared by every head; YaRN rotary frequencies and the softmax
  scale (qk dims) ** -0.5 * mscale(factor, mscale_all_dim) ** 2;
* DeepSeekMoE (§2.2) on an expert share: softmax over all the router's
  experts, top-k weights not renormalized, the share's experts computed
  densely over every token and gated by the weight of the tokens routed
  to them that fit the whole layer's per-expert capacity int(cf * S * k /
  E) in token order, plus the shared experts (one SiLU MLP); the
  expert-level balance loss per sequence (``seq_aux``);
* the leading dense layers, pre-norm RMSNorm, SwiGLU MLPs, a tied head.

The coded step is the decode-weighted sum of per-worker gradients over
the number of data groups (fractional repetition: worker i holds group
i mod G, decoded by this file's own rule from the step's straggler mask),
then global-norm clipping and AdamW.  Each group's gradient is taken one
row at a time (each layer rematerialized, the AdamW moments on the host)
so that the reference fits one chip at the cell's size.

``precision`` is as in ``chipbench.reference.lm``: "highest" (float32
products at full precision) or "int8" (the control).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import lm

# the configuration keys the forward pass reads
KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "first_k_dense_replace", "num_experts_per_tok", "router_experts",
        "n_routed_experts", "expert_offset", "capacity_factor",
        "rms_norm_eps", "rope_theta", "aux_loss_alpha")


def layout(cfg: dict) -> dict:
    """{path: (shape, fan_in)} of the weights (the program's parameter
    tree); fan_in 0 marks a norm offset."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    L0 = cfg["first_k_dense_replace"]
    L1 = cfg["num_hidden_layers"] - L0
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe

    def attention(group, L):
        return {
            (group, "0", "norm1"): ((L, d), 0),
            (group, "0", "norm2"): ((L, d), 0),
            (group, "0", "wq"): ((L, d, H, dq), d),
            (group, "0", "wkv_a"): ((L, d, r + cfg["qk_rope_head_dim"]), d),
            (group, "0", "kv_norm"): ((L, r), 0),
            (group, "0", "wkv_b"): ((L, r, H, cfg["qk_nope_head_dim"] + dv),
                                    r),
            (group, "0", "wo"): ((L, H, dv, d), H * dv),
        }

    lay = {("embed",): ((cfg["vocab_size"], d), d),
           ("final_norm",): ((d,), 0)}
    lay.update(attention("lead", L0))
    lay.update({("lead", "0", "ffn_wg"): ((L0, d, f), d),
                ("lead", "0", "ffn_wi"): ((L0, d, f), d),
                ("lead", "0", "ffn_wo"): ((L0, f, d), f)})
    lay.update(attention("blocks", L1))
    lay.update({
        ("blocks", "0", "router"): ((L1, d, cfg["router_experts"]), d),
        ("blocks", "0", "moe_wg"): ((L1, E, d, fe), d),
        ("blocks", "0", "moe_wi"): ((L1, E, d, fe), d),
        ("blocks", "0", "moe_wo"): ((L1, E, fe, d), fe),
        ("blocks", "0", "shared_wg"): ((L1, d, fs), d),
        ("blocks", "0", "shared_wi"): ((L1, d, fs), d),
        ("blocks", "0", "shared_wo"): ((L1, fs, d), fs),
    })
    return lay


# -- rotary embedding with YaRN scaling ---------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies: the dims below the one that turns
    beta_fast times over the original context keep theta^(-2i/dim), those
    above the one that turns beta_slow times are divided by the factor,
    with a linear ramp between."""
    L = rs["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(L / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    base = 1.0 / theta ** (2 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return base * (1.0 - ramp) + base / rs["factor"] * ramp


def rope_tables(S: int, cfg: dict):
    """cos, sin (S, rope/2) at positions 0..S-1."""
    rs = cfg["rope_scaling"]
    inv = yarn_inv_freq(cfg["qk_rope_head_dim"], cfg["rope_theta"], rs)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    mag = (yarn_mscale(rs["factor"], rs["mscale"])
           / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    return (jnp.asarray(np.cos(ang) * mag, jnp.float32),
            jnp.asarray(np.sin(ang) * mag, jnp.float32))


def _rotate(x, cos, sin):
    """x (S, h, rope): the halves rotated as one complex pair per
    frequency."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


# -- layers -------------------------------------------------------------------

def mla(p, h, cos, sin, cfg: dict, mm):
    """Causal multi-head latent attention of one row h (S, d)."""
    dn, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    S = h.shape[0]
    q = mm("sd,dhk->shk", h, p["wq"])
    kva = mm("sd,dk->sk", h, p["wkv_a"])
    c = lm._rmsnorm(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = mm("sr,rhk->shk", c, p["wkv_b"])
    k_rope = _rotate(kva[:, None, r:], cos, sin)            # (S, 1, rope)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (S, kv.shape[1],
                                                 k_rope.shape[-1]))], -1)
    v = kv[..., dn:]
    s = mm("qhk,shk->hqs", q, k) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    return mm("qhk,hkd->qd", o, p["wo"])


def _swiglu(h, wg, wi, wo, mm):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", h, wg))
              * mm("sd,df->sf", h, wi), wo)


def routing(logits, cfg: dict):
    """(gate (S, E_router): the weight each token gives each expert that
    kept it within capacity, probs (S, E_router), chosen (S, E_router) 0/1)
    of one row's router logits."""
    S, E = logits.shape
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # (S, k)
    chosen = jax.nn.one_hot(top_e, E)                       # (S, k, E)
    flat = chosen.reshape(S * k, E)                         # token order
    before = jnp.cumsum(flat, axis=0) - flat                # earlier, same e
    C = max(int(cfg["capacity_factor"] * S * k / E), 1)
    kept = (flat * (before < C)).reshape(S, k, E)
    gate = jnp.sum(kept * top_w[..., None], axis=1)
    return gate, probs, chosen.sum(1)


def moe(p, h, cfg: dict, mm):
    """(out (S, d), balance loss) of one row through the expert share."""
    S = h.shape[0]
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    lo, held = cfg["expert_offset"], cfg["n_routed_experts"]
    gate, probs, chosen = routing(mm("sd,de->se", h, p["router"]), cfg)
    out = _swiglu(h, p["shared_wg"], p["shared_wi"], p["shared_wo"], mm)
    for e in range(held):
        out = out + gate[:, lo + e, None] * _swiglu(
            h, p["moe_wg"][e], p["moe_wi"][e], p["moe_wo"][e], mm)
    f = chosen.sum(0) * E / (k * S)
    return out, jnp.sum(f * probs.mean(0))


def layer(p, x, cos, sin, cfg: dict, mm, expert: bool):
    """One pre-norm block of one row x (S, d) -> (x, balance loss)."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(p, lm._rmsnorm(x, p["norm1"], eps), cos, sin, cfg, mm)
    h = lm._rmsnorm(x, p["norm2"], eps)
    if expert:
        out, aux = moe(p, h, cfg, mm)
    else:
        out = _swiglu(h, p["ffn_wg"], p["ffn_wi"], p["ffn_wo"], mm)
        aux = jnp.zeros((), jnp.float32)
    return x + out, aux


def row_losses(params, tokens, labels, *, cfg: dict, precision: str):
    """(sum of the token cross entropies, sum over the expert layers of
    the balance loss) of one row tokens/labels (S,)."""
    mm = lm._mm(precision)
    cos, sin = rope_tables(tokens.shape[0], cfg)
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for group, expert in (("lead", False), ("blocks", True)):
        blk = params[group]["0"]
        step = jax.checkpoint(partial(layer, cfg=cfg, mm=mm, expert=expert))
        for i in range(blk["norm1"].shape[0]):
            x, a = step({n: v[i] for n, v in blk.items()}, x, cos, sin)
            aux = aux + a
    x = lm._rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm("sd,vd->sv", x, params["embed"]), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1)), aux


@partial(jax.jit, static_argnames=("cfg_items", "precision"),
         donate_argnums=(0,))
def _row_step(acc, params, tokens, labels, w_ce, w_aux, *, cfg_items,
              precision):
    """acc + the gradient of w_ce * CE + w_aux * balance of one row, and
    w_ce * CE."""
    cfg = {k: dict(v) if k == "rope_scaling" else v for k, v in cfg_items}

    def f(p):
        ce, aux = row_losses(p, tokens, labels, cfg=cfg, precision=precision)
        return w_ce * ce + w_aux * aux, w_ce * ce

    (_, ce), g = jax.value_and_grad(f, has_aux=True)(params)
    return jax.tree.map(jnp.add, acc, g), ce


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
         donate_argnums=(0,))
def _leaf_adamw(p, g, m, v, count, lr, *, b1, b2, eps, wd):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps) - lr * wd * p, m, v


def decode_weights(mask, m: int, groups: int) -> np.ndarray:
    """Fractional repetition: worker i holds group i mod G; each surviving
    replica of a group weighs 1 / (its group's survivors), and the weights
    are scaled by G / (groups that survive)."""
    mask = np.asarray(mask, np.float64)
    cluster = np.arange(m) % groups
    alive = np.bincount(cluster, weights=mask, minlength=groups)
    c = np.where(alive[cluster] > 0, mask / np.maximum(alive[cluster], 1), 0)
    return c * groups / max(int(np.sum(alive > 0)), 1)


def train(params0, steps, *, cfg: dict, total_steps: int,
          precision: str = "highest") -> dict:
    """The reference's first len(steps) coded steps from params0.

    steps: [(rows (G * rows, S + 1) int token ids, groups in order; mask
    (m,) the step's straggler mask), ...].  Returns {"loss": [...],
    "grad1": {path: norm of the clipped first gradient}, "change": {path:
    norm of params_T - params0}}."""
    opt, tr = cfg["optimizer"], cfg["trainer"]
    m, per = tr["m_workers"], tr["rows_per_worker"]
    G = m // tr["beta"]
    cfg_items = tuple(sorted((k, cfg[k]) for k in KEYS)) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)
    p = jax.tree.map(lambda a: jnp.array(a, jnp.float32), params0)  # a copy
    paths = sorted(lm.flat_leaves(p))
    mom = {k: np.zeros(lm.flat_leaves(p)[k].shape, np.float32)
           for k in paths}
    vel = {k: np.zeros_like(mom[k]) for k in paths}
    losses, grad1 = [], None
    for t, (rows, mask) in enumerate(steps):
        rows = np.asarray(rows)
        S = rows.shape[1] - 1
        c = decode_weights(mask, m, G)
        w_group = np.bincount(np.arange(m) % G, weights=c, minlength=G)
        acc = jax.tree.map(jnp.zeros_like, p)
        loss = 0.0
        for j in range(rows.shape[0]):                 # one row at a time
            w = w_group[j // per] / G
            acc, ce = _row_step(
                acc, p, jnp.asarray(rows[j, :-1]), jnp.asarray(rows[j, 1:]),
                jnp.float32(w / (per * S)),
                jnp.float32(w * cfg["aux_loss_alpha"] / per),
                cfg_items=cfg_items, precision=precision)
            loss += float(ce)
        flat_g = lm.flat_leaves(acc)
        gnorm = math.sqrt(sum(float(jnp.vdot(flat_g[k], flat_g[k]))
                              for k in paths))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        if t == 0:
            grad1 = {"/".join(k): scale * float(jnp.linalg.norm(flat_g[k]))
                     for k in paths}
        lr = jnp.float32(lm.lr_at(t, opt, total_steps))
        flat_p = lm.flat_leaves(p)
        new = {}
        for k in paths:
            new[k], mk, vk = _leaf_adamw(
                flat_p[k], flat_g[k] * scale, jnp.asarray(mom[k]),
                jnp.asarray(vel[k]), jnp.float32(t + 1), lr, b1=opt["b1"],
                b2=opt["b2"], eps=opt["eps"], wd=opt["weight_decay"])
            mom[k], vel[k] = np.asarray(mk), np.asarray(vk)
        del acc, flat_g, flat_p
        p = lm._nest(new)
        losses.append(loss)
    p0 = lm.flat_leaves(params0)
    change = {"/".join(k): float(jnp.linalg.norm(
        lm.flat_leaves(p)[k] - jnp.asarray(p0[k], jnp.float32)))
        for k in paths}
    return {"loss": losses, "grad1": grad1, "change": change}
