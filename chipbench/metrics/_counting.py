"""Work that a call requires, fixed whatever implements it.

Each function returns ``(flops, bytes)``: the operations the algorithm
needs and the HBM bytes it must move at least once.  An operand that every
realization of a call shares (``SX``, ``Sy``, ``X``, ``y``) is counted ONCE
per call, not once per realization, so a program that batches
realizations through one pass over it can reach 100% and no more.
"""
from __future__ import annotations

import math

F32 = 4


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least seconds the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])


def fused_gradient(m: int, r: int, p: int, R: int) -> tuple[float, float]:
    """The fused masked gradient over R realizations in one call.

    FLOPs 4*m*r*p per realization (residual matvec and transposed matvec);
    bytes: SX (m, r, p) and Sy (m, r) once, plus per realization the
    iterate in, the decode weights in and the gradient out."""
    flops = 4.0 * m * r * p * R
    nbytes = F32 * (m * r * p + m * r) + R * F32 * (2 * p + m)
    return flops, nbytes


def objective(n: int, p: int, R: int) -> tuple[float, float]:
    """f(w) = 1/(2n)||Xw - y||^2 + lam/2 ||w||^2 for R iterates: X and y
    read once, the R iterates read once."""
    flops = R * (2.0 * n * p + 3.0 * n + 2.0 * p)
    nbytes = F32 * (n * p + n) + R * F32 * p
    return flops, nbytes


def solver_iteration(n: int, p: int, m: int, r: int,
                     R: int) -> tuple[float, float]:
    """One iteration of coded GD or coded L-BFGS for R realizations: one
    pass over SX (the masked gradient) and one over X (the objective at
    eval_every=1), plus the vectors.  L-BFGS's recomputed previous-iterate
    gradient and its separate line-search pass over SX are not required
    work: the line search's S_i X d follows from the residuals."""
    gf, gb = fused_gradient(m, r, p, R)
    of, ob = objective(n, p, R)
    return gf + of, gb + ob


def encode(n: int, p: int, rows: int) -> tuple[float, float]:
    """The per-solve encode of [X | y] (n, p+1) into rows (rows, p+1): one
    pass in and one out; a fast transform of length ``rows`` per column
    (rows * log2(rows) additions)."""
    q = p + 1
    flops = float(rows) * math.log2(max(rows, 2)) * q
    nbytes = F32 * (n * q + rows * q)
    return flops, nbytes


def combine(m: int, P: int) -> tuple[float, float]:
    """The decode-weighted combine of an (m, P) f32 stack: m*P read, P
    written."""
    return 2.0 * m * P, F32 * (m * P + P + m)


def lm_params(d: int, n_layers: int, n_heads: int, head_dim: int,
              d_ff: int, vocab: int) -> dict:
    """Parameter counts of a dense llama-style decoder with tied
    embeddings: attention, gated MLP, norms, embedding."""
    attn = 4 * d * n_heads * head_dim
    mlp = 3 * d * d_ff
    per_layer = attn + mlp + 2 * d
    return {"layer": per_layer, "layers": n_layers * per_layer,
            "embed": vocab * d, "total": n_layers * per_layer + vocab * d + d}


def lm_train_flops_per_token(d: int, n_layers: int, n_heads: int,
                             head_dim: int, d_ff: int, vocab: int,
                             seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward.

    6 x the matrix parameters a token passes through (every layer's
    projections and the tied output head, V*d; the embedding lookup is
    free), plus causal attention: scores and values take 2*S*d per token
    per layer forward on average (half of the S x S square), 3x for
    forward and backward, so 6*S*d per token per layer.  Recomputation
    does not count."""
    matmul_params = n_layers * (4 * d * n_heads * head_dim + 3 * d * d_ff)
    matmul_params += vocab * d
    attn = 6.0 * n_layers * seq_len * n_heads * head_dim
    return 6.0 * matmul_params + attn
