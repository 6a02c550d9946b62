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

Given as many devices as workers, the trainer instead places worker i on
device i (``build_worker_per_chip_step``): ``shard_map`` over a
``workers`` mesh axis, each device holding its worker's tokens, labels,
coefficients and decode weight, a replica of the weights and a quarter
(1/m) of the AdamW moments; its gradient, times its decode weight in f32,
joins a cross-device reduce-scatter (the decode-weighted combine as a
collective), AdamW updates the device's part of the weights from its part
of the sum over ``num_groups``, and an all-gather rebuilds the replica.  No
(m, P) stack is built, and the weights and moments are donated, so a
device holds one copy of each.

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
from ..optim import AdamWState, adamw_init, adamw_update, cosine_schedule
from ..runtime.engine import ClusterEngine, FastestK, _policy_k_min
from ..runtime.strategies import UnsupportedStrategy

__all__ = ["TrainerConfig", "TrainProblem", "build_coded_train_step",
           "build_worker_per_chip_step", "split_opt_state", "CodedTrainer",
           "run_coded_sgd"]

# counters an expert layer adds to a forward's aux (models/moe.py)
_MOE_COUNTS = ("moe_routed", "moe_dropped", "moe_peak")


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


def _worker_loss(cfg: ArchConfig, rows_per_group: int,
                 z_loss_weight: float) -> Callable:
    """(params, tokens, labels, coeff) -> (total, (ce, counts)) of one
    worker's shard: tokens/labels (g, S), coeff (g,).  ``counts`` holds the
    expert layers' counters (empty for a dense model)."""
    if cfg.n_patches or cfg.n_enc_layers:
        raise UnsupportedStrategy("coded-sgd covers token-only LMs (no patch/encoder "
                         "modalities in the coded worker layout)")
    from ..models import transformer as T

    def worker_loss(params, tokens, labels, coeff):
        logits, aux = T.forward(params, cfg, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        denom = float(rows_per_group * labels.shape[-1])
        ce = -(ll * coeff[:, None]).sum() / denom
        scale = coeff.mean()
        total = ce + scale * (
            cfg.router_aux_weight * aux.get("load_balance", 0.0)
            + z_loss_weight * aux.get("router_z", 0.0))
        return total, (ce, {k: aux[k] for k in _MOE_COUNTS if k in aux})

    return worker_loss


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
    worker_loss = _worker_loss(cfg, rows_per_group, z_loss_weight)

    def step(params, opt_state, tokens, labels, coeff, decode):
        (losses_all, (losses_ce, counts)), grads = jax.vmap(
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
        counts = {k: v.sum() for k, v in counts.items()}
        return params, opt_state, {"loss": loss, "lr": lr, **om, **counts}

    return step


def _rows(x, n: int):
    """``x`` flattened, zero-padded to a multiple of ``n`` and cut into
    ``n`` rows: row i is device i's part."""
    flat = x.reshape(-1)
    flat = jnp.pad(flat, (0, -flat.size % n))
    return flat.reshape(n, -1)


def split_opt_state(opt: AdamWState, params, mesh) -> AdamWState:
    """``opt`` in the worker-per-chip step's layout: each moment leaf
    flattened and zero-padded as ``_rows`` does, and split over the mesh's
    ``workers`` axis; the count replicated.  A leaf already in that layout
    is left as it is."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape["workers"]
    split = NamedSharding(mesh, P("workers"))

    def lay(x, p):
        if x.shape != (p.size + -p.size % n,):
            x = _rows(x, n).reshape(-1)
        return jax.device_put(x, split)
    moments = lambda t: jax.tree.map(lay, t, params)
    return AdamWState(moments(opt.m), moments(opt.v),
                      jax.device_put(opt.count, NamedSharding(mesh, P())))


def build_worker_per_chip_step(cfg: ArchConfig, lr_fn: Callable, mesh, *,
                               rows_per_group: int, num_groups: int,
                               weight_decay: float = 0.1,
                               z_loss_weight: float = 1e-3) -> Callable:
    """The coded step with worker i on device i of ``mesh`` (axis
    ``workers``, one device per worker); the arguments and results are
    those of ``build_coded_train_step``, with tokens, labels, coeff and
    decode split over the axis, the weights and metrics replicated and the
    AdamW state in ``split_opt_state``'s layout.

    A device computes its worker's gradient, scales it by its decode
    weight in f32 and joins one reduce-scatter over the axis (named scope
    ``coded_combine``), which leaves it its part of the sum; divided by
    ``num_groups`` and cast back to the weights' dtype, that part and the
    device's part of the moments update its part of the weights (AdamW,
    the clipping norm taken over all parts), and an all-gather (named
    scope ``weights_gather``) gives every device the whole again."""
    from jax.sharding import PartitionSpec as P
    worker_loss = _worker_loss(cfg, rows_per_group, z_loss_weight)
    n = mesh.shape["workers"]

    def local(params, opt_state, tokens, labels, coeff, decode):
        (_, (ce, counts)), grads = jax.value_and_grad(
            worker_loss, has_aux=True)(params, tokens[0], labels[0], coeff[0])
        c = decode[0]
        with jax.named_scope("coded_combine"):
            grads = jax.tree.map(
                lambda g: (jax.lax.psum_scatter(
                    _rows(g.astype(jnp.float32) * c, n), "workers")
                    / num_groups).astype(g.dtype), grads)
            loss = jax.lax.psum(c * ce, "workers") / num_groups
            counts = jax.lax.psum(counts, "workers")
        i = jax.lax.axis_index("workers")
        mine = jax.tree.map(lambda p: _rows(p, n)[i], params)
        lr = lr_fn(opt_state.count)
        mine, opt_state, om = adamw_update(
            grads, opt_state, mine, lr=lr, weight_decay=weight_decay,
            axis_name="workers")
        with jax.named_scope("weights_gather"):
            params = jax.tree.map(
                lambda s, p: jax.lax.all_gather(s, "workers", tiled=True)[
                    :p.size].reshape(p.shape), mine, params)
        return params, opt_state, {"loss": loss, "lr": lr, **om, **counts}

    rep, split = P(), P("workers")
    state = AdamWState(split, split, rep)
    # check_vma=False: the model's scans (attention chunks, layers) start
    # from unvarying zero carries that the body makes per-worker, which the
    # varying-axes check rejects; the outputs are collective results and
    # what AdamW makes of them, the same on every device
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(rep, state, split, split, split, split),
                         out_specs=(rep, state, rep), check_vma=False)


class CodedTrainer:
    """Engine-driven coded training loop (DESIGN §15).

    Straggler/fault realization, active-set policy and wall-clock all come
    from one pre-sampled ``ClusterEngine`` schedule (so runs are resumable
    and bit-reproducible per engine seed); per-step host time is split into
    compile/execute via ``obs.timing.CompileWatch``; the realized schedule
    lands on the active obs recorder and is kept as ``last_schedule``.

    ``devices``: as many as workers places one worker on each
    (``build_worker_per_chip_step``; the step donates the weights and
    state, so a caller keeps no reference to what it passes in); None or
    one device runs every worker on one device under vmap.
    """

    def __init__(self, cfg: ArchConfig, tcfg: TrainerConfig,
                 engine: ClusterEngine, policy=None, degrade=None,
                 devices=None):
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
        self.mesh = None
        self._put = jnp.asarray
        if devices is not None and len(devices) > 1:
            if len(devices) != tcfg.m_workers:
                raise ValueError(f"{len(devices)} devices for "
                                 f"{tcfg.m_workers} workers: give one "
                                 f"device per worker, or one")
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self.mesh = Mesh(np.asarray(devices), ("workers",))
            split = NamedSharding(self.mesh, PartitionSpec("workers"))
            self._put = lambda a: jax.device_put(a, split)
            self._step = jax.jit(build_worker_per_chip_step(
                cfg, lr_fn, self.mesh, rows_per_group=tcfg.rows_per_worker,
                num_groups=self.code.num_groups), donate_argnums=(0, 1))
        else:
            self._step = jax.jit(build_coded_train_step(
                cfg, lr_fn, rows_per_group=tcfg.rows_per_worker,
                num_groups=self.code.num_groups))
        self.last_schedule = None
        # ``steps`` run and ``host_syncs``: the points of a step at which
        # the host waits on the device; for an expert model ``moe_routed``
        # and ``moe_dropped``: assignments to the experts held and those
        # dropped past capacity, and ``moe_peak``: a row's assignments to
        # its most loaded held expert, each summed over rows, workers,
        # layers and steps
        self.metrics = MetricsRegistry()

    def place(self, params, opt=None):
        """The weights and AdamW state (a fresh one if ``opt`` is None) in
        the step's layout: as they are on one device; over a mesh, the
        weights replicated and the state as ``split_opt_state`` lays it."""
        dtype = jnp.dtype(self.cfg.optstate_dtype)
        if self.mesh is None:
            return params, (opt if opt is not None
                            else adamw_init(params, dtype=dtype))
        from jax.sharding import NamedSharding, PartitionSpec
        params = jax.device_put(params,
                                NamedSharding(self.mesh, PartitionSpec()))
        if opt is None:
            split = NamedSharding(self.mesh, PartitionSpec("workers"))
            n = self.mesh.size
            zeros = lambda: jax.tree.map(
                lambda p: jnp.zeros((p.size + -p.size % n,), dtype,
                                    device=split), params)
            opt = AdamWState(zeros(), zeros(), jnp.zeros((), jnp.int32))
        return params, split_opt_state(opt, params, self.mesh)

    def init_state(self, key=None):
        from ..models import transformer as T
        key = key if key is not None else jax.random.key(self.tcfg.seed)
        return self.place(T.init_params(self.cfg, key))

    def run(self, params=None, opt=None, callback: Optional[Callable] = None):
        if params is None:
            params, opt = self.init_state()
        params, opt = self.place(params, opt)
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
                                params, opt, self._put(tokens),
                                self._put(labels), self._put(coeff),
                                self._put(decode))
                        with _obs_span("train:wait"):
                            params, opt, metrics = block(out)
                    with _obs_span("train:readback"):
                        if _MOE_COUNTS[0] in metrics:
                            # one read of the scalars and the counters
                            loss, gnorm, *counts = jax.device_get(
                                [metrics["loss"], metrics["grad_norm"]]
                                + [metrics[k] for k in _MOE_COUNTS])
                            for k, n in zip(_MOE_COUNTS, counts):
                                self.metrics.counter(k).inc(int(n))
                            reads = 1
                        else:
                            loss = float(metrics["loss"])
                            gnorm = float(metrics["grad_norm"])
                            reads = 2
                        rec = {"step": t, "loss": float(loss),
                               "grad_norm": float(gnorm),
                               "sim_time_s": float(sched.times[t]),
                               "active": int((mask > 0).sum()),
                               "exact": bool(
                                   code_t.decode_exact_possible(mask)),
                               "host_s": cw.total_s,
                               "compile_s": cw.compile_s,
                               "execute_s": cw.execute_s,
                               "compiles": cw.compiles}
                        history.append(rec)
                    # the host waits on the device at block and at each
                    # read; FRC decode weights are also read back
                    steps.inc()
                    syncs.inc(1 + reads + isinstance(code_t, FRCode))
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
