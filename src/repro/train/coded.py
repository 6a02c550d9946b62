"""repro.train.coded — coded SGD bridging the model zoo to the runtime.

The subsystem DESIGN §15 describes: per-worker minibatch gradients of a real
neural LM flow through the gradient-coding combine, and the training loop is
driven by the SAME ``ClusterEngine`` schedules, active-set policies, fault
injectors and wall-clock accounting as every convex strategy — the legacy
self-contained loop in ``train/trainer.py`` is now a thin adapter over
:class:`CodedTrainer`.

Dataflow per step t (one jitted program after the first step):

    GroupBatcher ----> tokens/labels (m, g*rows, S), coeff (m, g*rows)
    Schedule.masks[t] -> code.decode_weights(mask)        (host, tiny)
    vmap(value_and_grad(worker_loss)) over the worker axis
        worker i: sum_r coeff[i,r] * CE_row_r / (rows * S)   [+ aux]
    flatten grads -> ONE (m, P_total) block
    kernels.coded_reduce.coded_combine_call(block, decode) / num_groups
    optim.adamw_update

The per-row cross entropy uses a FIXED denominator (rows * S tokens), not
the self-normalizing ``lm_loss`` weight sum: gradients stay LINEAR in the
combine coefficients, so with an exact code the decoded update equals the
full-batch update bit-for-bit (tests/test_coded_sgd.py) and a stochastic
code is unbiased (tests/test_code_properties.py).

``run_coded_sgd`` adapts the trainer to the Strategy interface
(``RunResult`` with engine times as the x-axis); ``runtime.strategies``
registers it as ``coded-sgd``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.gradient_coding import FRCode, GradientCode, make_code
from ..data.pipeline import GroupBatcher, TokenStream
from ..kernels.coded_reduce import coded_combine_call
from ..obs.metrics import MetricsRegistry
from ..obs.timing import CompileWatch, block
from ..obs.trace import span as _obs_span
from ..optim import adamw_init, adamw_update, cosine_schedule
from ..runtime.engine import ClusterEngine, FastestK, _policy_k_min
from ..runtime.strategies import UnsupportedStrategy

__all__ = ["TrainerConfig", "TrainProblem", "build_coded_train_step",
           "CodedTrainer", "run_coded_sgd"]


@dataclasses.dataclass
class TrainerConfig:
    """Loop configuration (canonical home; ``train.trainer`` re-exports)."""
    m_workers: int = 8            # coded-DP worker shards
    beta: int = 2                 # code redundancy degree
    wait_k: int = 6               # fastest-k the master waits for
    rows_per_worker: int = 1      # sequences per data GROUP (per slot)
    seq_len: int = 128
    steps: int = 100
    lr: float = 3e-4
    warmup: int = 20
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    log_every: int = 10
    uncoded: bool = False         # baseline: no redundancy (beta=1)
    code: Optional[str] = None    # gradient code name; None -> frc/uncoded


@dataclasses.dataclass(frozen=True)
class TrainProblem:
    """The ``ProblemSpec`` analogue for ``train``-kind cells: which LM to
    train on the synthetic token stream (experiments/spec.py builds one per
    ``ProblemAxis(kind='train')``)."""
    arch: str = "deepseek-7b"
    preset: str = "smoke"         # "smoke" | "100m"
    seq_len: int = 64
    rows_per_worker: int = 1
    vocab: int = 512

    def build_cfg(self) -> ArchConfig:
        from ..configs import ARCHS
        base = ARCHS[self.arch]
        if self.preset == "100m":
            # ~100M params: 12L x 768, tied embeddings (examples/train_lm.py)
            return base.with_overrides(
                n_layers=12, d_model=768, n_heads=12, n_kv=12, d_ff=2048,
                vocab=16384, head_dim=64, dtype="float32",
                param_dtype="float32", attn_chunk=256)
        if self.preset == "smoke":
            return base.smoke_variant().with_overrides(vocab=self.vocab)
        raise ValueError(f"unknown train preset '{self.preset}' "
                         f"(have: smoke, 100m)")


def build_coded_train_step(cfg: ArchConfig, lr_fn: Callable, *,
                           rows_per_group: int, num_groups: int,
                           weight_decay: float = 0.1,
                           z_loss_weight: float = 1e-3) -> Callable:
    """(params, opt_state, tokens, labels, coeff, decode) ->
    (params, opt_state, metrics).

    tokens/labels: (m, g, S) int32 — worker-major coded layout from
    ``GroupBatcher``; coeff: (m, g) f32 LOCAL combine coefficients
    (B[i, group_of_row]); decode: (m,) f32 decode weights c(A_t).

    The full-gradient estimate is  (1/num_groups) sum_i c_i grad_i  with
    grad_i the gradient of worker i's coefficient-weighted fixed-denominator
    CE — computed as one vmap over the worker axis and ONE fused
    ``coded_combine_call`` over the flattened (m, P_total) gradient block.
    Router aux losses ride along scaled by the mean local coefficient, so
    they pass through the same (unbiased) combine.
    """
    if cfg.n_patches or cfg.n_enc_layers:
        raise UnsupportedStrategy("coded-sgd covers token-only LMs (no patch/encoder "
                         "modalities in the coded worker layout)")
    from ..models import transformer as T

    def worker_loss(params, tokens, labels, coeff):
        # tokens/labels (g, S); coeff (g,) — one worker's shard
        logits, aux = T.forward(params, cfg, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        denom = float(rows_per_group * labels.shape[-1])
        ce = -(ll * coeff[:, None]).sum() / denom
        scale = coeff.mean()
        total = ce + scale * (
            cfg.router_aux_weight * aux.get("load_balance", 0.0)
            + z_loss_weight * aux.get("router_z", 0.0))
        return total, ce

    def step(params, opt_state, tokens, labels, coeff, decode):
        (losses_all, losses_ce), grads = jax.vmap(
            jax.value_and_grad(worker_loss, has_aux=True),
            in_axes=(None, 0, 0, 0))(params, tokens, labels, coeff)
        del losses_all
        m = tokens.shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        flat = jnp.concatenate(
            [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
        combined = coded_combine_call(flat, decode) / num_groups
        out, off = [], 0
        for l in leaves:
            size = l[0].size
            out.append(combined[off:off + size].reshape(l.shape[1:])
                       .astype(l.dtype))
            off += size
        grads = jax.tree_util.tree_unflatten(treedef, out)
        loss = jnp.dot(decode, losses_ce) / num_groups
        lr = lr_fn(opt_state.count)
        params, opt_state, om = adamw_update(
            grads, opt_state, params, lr=lr, weight_decay=weight_decay)
        return params, opt_state, {"loss": loss, "lr": lr, **om}

    return step


class CodedTrainer:
    """Engine-driven coded training loop (DESIGN §15).

    Straggler/fault realization, active-set policy and wall-clock all come
    from one pre-sampled ``ClusterEngine`` schedule (so runs are resumable
    and bit-reproducible per engine seed); per-step host time is split into
    compile/execute via ``obs.timing.CompileWatch``; the realized schedule
    lands on the active obs recorder and is kept as ``last_schedule``.
    """

    def __init__(self, cfg: ArchConfig, tcfg: TrainerConfig,
                 engine: ClusterEngine, policy=None, degrade=None):
        self.cfg, self.tcfg = cfg, tcfg
        if engine.m != tcfg.m_workers:
            raise ValueError(f"engine has m={engine.m} workers but "
                             f"TrainerConfig.m_workers={tcfg.m_workers}")
        name = tcfg.code or ("uncoded" if tcfg.uncoded else "frc")
        beta = 1 if tcfg.uncoded else tcfg.beta
        self.code: GradientCode = make_code(name, tcfg.m_workers, beta=beta,
                                            seed=tcfg.seed)
        self.stream = TokenStream(cfg.vocab, seed=tcfg.seed)
        self.batcher = GroupBatcher(self.stream, self.code,
                                    tcfg.rows_per_worker, tcfg.seq_len,
                                    seed=tcfg.seed)
        self.engine = engine
        self.policy = policy if policy is not None else FastestK(tcfg.wait_k)
        if degrade is not None and degrade.mode == "hold":
            raise UnsupportedStrategy("coded-sgd supports renormalize/backoff degrade "
                             "only (the decode weights renormalize over the "
                             "active set by construction; see DESIGN.md §15)")
        self.degrade = degrade
        lr_fn = cosine_schedule(tcfg.lr, tcfg.warmup, tcfg.steps)
        self._step = jax.jit(build_coded_train_step(
            cfg, lr_fn, rows_per_group=tcfg.rows_per_worker,
            num_groups=self.code.num_groups))
        self.last_schedule = None
        # ``steps`` run and ``host_syncs``: the points of a step at which
        # the host waits on the device
        self.metrics = MetricsRegistry()

    def init_state(self, key=None):
        from ..models import transformer as T
        key = key if key is not None else jax.random.key(self.tcfg.seed)
        params = T.init_params(self.cfg, key)
        opt = adamw_init(params, dtype=jnp.dtype(self.cfg.optstate_dtype))
        return params, opt

    def run(self, params=None, opt=None, callback: Optional[Callable] = None):
        if params is None:
            params, opt = self.init_state()
        tc = self.tcfg
        sched = self.engine.sample_schedule(tc.steps, self.policy,
                                            degrade=self.degrade)
        self.last_schedule = sched
        history = []
        steps, syncs = (self.metrics.counter("steps"),
                        self.metrics.counter("host_syncs"))
        with _obs_span("train:coded", code=self.code.codename,
                       steps=tc.steps, m=tc.m_workers):
            for t in range(tc.steps):
                with _obs_span("train:step", step=t):
                    with _obs_span("train:batch"):
                        code_t = self.code.at_step(t)
                        tokens, labels, coeff = self.batcher.next_batch(
                            code_t)
                    with _obs_span("train:decode"):
                        mask = np.asarray(sched.masks[t])
                        decode = code_t.decode_weights(mask)
                    with CompileWatch() as cw:
                        with _obs_span("train:dispatch"):
                            out = self._step(
                                params, opt, jnp.asarray(tokens),
                                jnp.asarray(labels), jnp.asarray(coeff),
                                jnp.asarray(decode))
                        with _obs_span("train:wait"):
                            params, opt, metrics = block(out)
                    with _obs_span("train:readback"):
                        rec = {"step": t, "loss": float(metrics["loss"]),
                               "grad_norm": float(metrics["grad_norm"]),
                               "sim_time_s": float(sched.times[t]),
                               "active": int((mask > 0).sum()),
                               "exact": bool(
                                   code_t.decode_exact_possible(mask)),
                               "host_s": cw.total_s,
                               "compile_s": cw.compile_s,
                               "execute_s": cw.execute_s,
                               "compiles": cw.compiles}
                        history.append(rec)
                    # the host waits on the device at block and at the two
                    # float reads; FRC decode weights are also read back
                    steps.inc()
                    syncs.inc(3 + isinstance(code_t, FRCode))
                    with _obs_span("train:callback"):
                        if callback:
                            callback(rec)
                    if tc.log_every and t % tc.log_every == 0:
                        print(f"step {t:5d} loss {rec['loss']:.4f} "
                              f"gnorm {rec['grad_norm']:.3f} "
                              f"active {rec['active']}/{tc.m_workers} "
                              f"simtime {rec['sim_time_s']:.1f}s",
                              flush=True)
                    if (tc.checkpoint_dir and tc.checkpoint_every
                            and (t + 1) % tc.checkpoint_every == 0):
                        from ..checkpoint import save
                        save(tc.checkpoint_dir, t + 1, (params, opt))
        return params, opt, history


def run_coded_sgd(spec: TrainProblem, engine: ClusterEngine, *,
                  steps: int = 100, **cfg):
    """Strategy-interface adapter: one coded-SGD run as a ``RunResult``
    whose times axis is the engine's simulated wall-clock.

    cfg keys: policy (ActiveSetPolicy), k (FastestK shorthand), code
    (gradient code name), beta, lr, warmup, log_every, seed, degrade
    (parsed ``DegradePolicy``), checkpoint_dir/checkpoint_every.  Unknown
    keys raise ``ValueError`` (the executor's skip path).
    """
    from ..runtime.strategies import RunResult, _fault_meta, _resolve_degrade

    policy = cfg.pop("policy", None)
    k = cfg.pop("k", None)
    if policy is None:
        policy = FastestK(k if k is not None else max(1, (3 * engine.m) // 4))
    degrade = _resolve_degrade(policy, cfg)
    code = cfg.pop("code", None) or "frc"
    beta = int(cfg.pop("beta", 2))
    tcfg = TrainerConfig(
        m_workers=engine.m, beta=beta, wait_k=_policy_k_min(policy),
        rows_per_worker=spec.rows_per_worker, seq_len=spec.seq_len,
        steps=steps, lr=float(cfg.pop("lr", 3e-3)),
        warmup=int(cfg.pop("warmup", min(10, max(1, steps // 5)))),
        seed=int(cfg.pop("seed", engine.seed)),
        checkpoint_dir=cfg.pop("checkpoint_dir", None),
        checkpoint_every=int(cfg.pop("checkpoint_every", 0)),
        log_every=int(cfg.pop("log_every", 0)),
        uncoded=(str(code).lower() in ("uncoded", "none")), code=str(code))
    if cfg:
        raise ValueError(f"unknown coded-sgd config keys {sorted(cfg)}")
    trainer = CodedTrainer(spec.build_cfg(), tcfg, engine, policy=policy,
                           degrade=degrade)
    _, _, hist = trainer.run()
    sched = trainer.last_schedule
    meta = {"arch": spec.arch, "preset": spec.preset,
            "code": trainer.code.codename, "beta": trainer.code.beta
            if hasattr(trainer.code, "beta") else beta,
            "policy": type(policy).__name__,
            "seq_len": spec.seq_len, "rows_per_worker": spec.rows_per_worker,
            "mean_active": float(np.mean([r["active"] for r in hist])),
            "exact_fraction": float(np.mean([r["exact"] for r in hist])),
            "host_s": float(sum(r["host_s"] for r in hist)),
            "compile_s": float(sum(r["compile_s"] for r in hist)),
            "compiles": int(sum(r["compiles"] for r in hist)),
            **_fault_meta(engine, policy, degrade, sched.masks)}
    return RunResult(
        strategy="coded-sgd",
        times=np.asarray([r["sim_time_s"] for r in hist]),
        objective=np.asarray([r["loss"] for r in hist]),
        w=None, meta=meta, schedule=sched)
