"""Plain reference of one coded training step of a llama-style decoder.

Written from the published architecture (DeepSeek LLM, arXiv:2401.02954:
pre-norm RMSNorm, rotary embeddings on half-split head dimensions with
theta 1e4, multi-head causal attention, SwiGLU MLP) in float32, importing
nothing of the program.  With an exact gradient code every decoded update
equals the plain full-batch one, so the reference is plain data-parallel
training: the mean token cross entropy over every distinct row of the
step, its gradient, global-norm clipping and AdamW.

The weights follow the program's parameter layout (``layout``), in which
the RMSNorm scale is 1 + offset and the output head is tied to the token
embedding; the benchmark draws them itself (``init``).

``precision="highest"`` runs every matrix product in float32 at full
precision.  ``precision="int8"`` rounds both operands of every product, in
the forward and the backward pass, to int8 with a per-tensor scale: the
control, one precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def layout(d: int, n_layers: int, n_heads: int, hd: int, d_ff: int,
           vocab: int) -> dict:
    """{path: (shape, fan_in)} of the weights; fan_in 0 marks a norm
    offset (zeros)."""
    L = n_layers
    return {
        ("embed",): ((vocab, d), d),
        ("final_norm",): ((d,), 0),
        ("blocks", "0", "norm1"): ((L, d), 0),
        ("blocks", "0", "norm2"): ((L, d), 0),
        ("blocks", "0", "wq"): ((L, d, n_heads, hd), d),
        ("blocks", "0", "wk"): ((L, d, n_heads, hd), d),
        ("blocks", "0", "wv"): ((L, d, n_heads, hd), d),
        ("blocks", "0", "wo"): ((L, n_heads, hd, d), n_heads * hd),
        ("blocks", "0", "ffn_wg"): ((L, d, d_ff), d),
        ("blocks", "0", "ffn_wi"): ((L, d, d_ff), d),
        ("blocks", "0", "ffn_wo"): ((L, d_ff, d), d_ff),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def init(lay: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Weights from the seed, on the device, in one jitted call:
    N(0, 1/fan_in) for projections and the embedding, zeros for norm
    offsets."""
    paths = sorted(lay)

    @jax.jit
    def make(key):
        flat = {}
        for i, path in enumerate(paths):
            shape, fan = lay[path]
            if fan == 0:
                flat[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              / math.sqrt(fan)).astype(dtype)
        return _nest(flat)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return make(key)


def flat_leaves(tree: dict) -> dict:
    """{path: leaf} of a nested dict."""
    out = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (k,))
        else:
            out[path] = node
    walk(tree, ())
    return out


def _q8(x):
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_int8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HI)


def _mm_int8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)


def _mm_int8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HI),
                     qa, qb)
    return vjp(_q8(g))


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm(precision: str):
    if precision == "int8":
        return _mm_int8
    return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + offset)


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def row_loss_sum(params, tokens, labels, *, cfg: dict, precision: str):
    """Sum of the token cross entropies of rows tokens/labels (B, S)."""
    mm = _mm(precision)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"][tokens]
    blk = params["blocks"]["0"]
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for layer in range(blk["wq"].shape[0]):
        p = {k: v[layer] for k, v in blk.items()}
        h = _rmsnorm(x, p["norm1"], eps)
        q = _rope(mm("bsd,dhk->bshk", h, p["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", h, p["wk"]), theta)
        v = mm("bsd,dhk->bshk", h, p["wv"])
        s = mm("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("bqhk,hkd->bqd", o, p["wo"])
        h = _rmsnorm(x, p["norm2"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, p["ffn_wg"]))
        x = x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, p["ffn_wi"]),
                   p["ffn_wo"])
    x = _rmsnorm(x, params["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, params["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _row_grad(params, tokens, labels, denom, *, cfg_items, precision):
    f = lambda p: row_loss_sum(p, tokens, labels, cfg=dict(cfg_items),
                               precision=precision) / denom
    return jax.value_and_grad(f)(params)


def lr_at(step: int, opt: dict, total: int) -> float:
    """The trainer's cosine schedule with linear warmup."""
    base, warm = opt["lr"], max(opt["warmup"], 1)
    if step < opt["warmup"]:
        return base * (step + 1.0) / warm
    frac = min(max((step - opt["warmup"]) / max(total - opt["warmup"], 1),
                   0.0), 1.0)
    return base * 0.5 * (1.0 + math.cos(math.pi * frac))


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"))
def _adamw(params, grads, m, v, count, lr, *, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps)
        - lr * wd * p, params, m, v)
    return params, m, v, g


def train(params0, batches, *, cfg: dict, total_steps: int,
          precision: str = "highest") -> dict:
    """The reference's first len(batches) steps from params0.

    batches: [(tokens (G, S), labels (G, S)), ...], the step's distinct
    rows.  Returns {"loss": [...], "grad1": {path: norm of the clipped
    first gradient}, "change": {path: norm of params_T - params0}}."""
    opt = cfg["optimizer"]
    cfg_items = tuple(sorted((k, cfg[k]) for k in
                             ("rms_norm_eps", "rope_theta")))
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad1 = [], None
    for t, (tokens, labels) in enumerate(batches):
        denom = float(np.prod(np.shape(tokens)))
        loss, grads = 0.0, None
        for row in range(np.shape(tokens)[0]):          # one row at a time
            l_r, g_r = _row_grad(p, jnp.asarray(tokens[row:row + 1]),
                                 jnp.asarray(labels[row:row + 1]),
                                 jnp.float32(denom), cfg_items=cfg_items,
                                 precision=precision)
            loss += float(l_r)
            grads = g_r if grads is None else jax.tree.map(jnp.add, grads,
                                                           g_r)
        p, m, v, g = _adamw(p, grads, m, v, jnp.float32(t + 1),
                            jnp.float32(lr_at(t, opt, total_steps)),
                            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                            wd=opt["weight_decay"], clip=opt["clip_norm"])
        losses.append(loss)
        if t == 0:
            grad1 = leaf_norms(g)
    p0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    change = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return {"loss": losses, "grad1": grad1, "change": change}


def leaf_norms(tree) -> dict:
    """{path string: float32 L2 norm} of every leaf."""
    return {"/".join(k): float(jnp.linalg.norm(jnp.asarray(v, jnp.float32)))
            for k, v in flat_leaves(tree).items()}


def host_change_norms(after, before) -> dict:
    """{path string: L2 norm of after - before} of host trees, in
    float64."""
    b = flat_leaves(before)
    return {"/".join(k): float(np.linalg.norm(
        np.asarray(v, np.float64) - np.asarray(b[k], np.float64)))
        for k, v in flat_leaves(after).items()}


def norm_gaps(got: dict, want: dict, keep) -> float:
    """Worst leaf's |norm_got - norm_want| / max(norm_want, median leaf's
    norm_want), over the leaves in ``keep``."""
    med = float(np.median([want[k] for k in keep]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keep)


def moved_leaves(grad1: dict) -> list:
    """Leaves whose reference gradient is not nought to rounding: norm at
    least a thousandth of the median leaf's."""
    med = float(np.median(list(grad1.values())))
    return sorted(k for k, v in grad1.items() if v >= 1e-3 * med)
