"""Driver for coded training: ``CodedTrainer.run`` on the program's step.

Set-up builds one ``CodedTrainer`` with the configuration's widths, draws
the weights from the seed on the device (``chipbench.reference.lm.init``)
and feeds the program's ``GroupBatcher`` from a token source of the
benchmark's own, which draws uniform ids from the seed and keeps the rows
of the first steps.  It then drives that trainer through its first steps
(the check's steps, which also compile the step) and hands the same
trainer and state to the window.  The window is one ``run`` call, ended by
its per-step callback once ``--seconds`` have passed; a step is the gap
between consecutive callbacks.  After the window the reference
(``chipbench.reference.lm``) trains from the same weights on the same rows.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench.reference import lm as ref
from chipbench.window import WindowRecord, p95, rate


class _WindowClosed(Exception):
    """Raised by the window's callback to end ``CodedTrainer.run``."""


class SeededTokens:
    """Token rows for the program's batcher: uniform ids over the
    vocabulary from the seed.  The first ``keep`` draws are kept for the
    reference."""

    def __init__(self, vocab: int, seed: int, keep: int):
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 3])
        self.keep = keep
        self.kept: list = []

    def sample(self, _rng, n: int, seq: int) -> np.ndarray:
        toks = self.rng.integers(0, self.vocab, size=(n, seq + 1),
                                 dtype=np.int32)
        if len(self.kept) < self.keep:
            self.kept.append(toks.copy())
        return toks


class Session:
    def __init__(self, cfg: dict, wl: dict, seed: int, devices):
        self.cfg, self.wl, self.seed = cfg, wl, int(seed)
        self.devices = devices

    def arch(self):
        from repro.configs import ARCHS
        c = self.cfg
        return ARCHS[c["arch"]].with_overrides(
            n_layers=c["num_hidden_layers"], vocab=c["vocab_size"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            head_dim=c["head_dim"], rope_theta=c["rope_theta"],
            param_dtype=c["param_dtype"], dtype=c["compute_dtype"],
            optstate_dtype=c["optstate_dtype"])

    def layout(self) -> dict:
        c = self.cfg
        return ref.layout(c["hidden_size"], c["num_hidden_layers"],
                          c["num_attention_heads"], c["head_dim"],
                          c["intermediate_size"], c["vocab_size"])

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        self.build()
        self.first_steps()

    def build(self) -> None:
        """The trainer, fed by the benchmark's token source."""
        from repro.data.pipeline import GroupBatcher
        from repro.runtime.engine import ClusterEngine, make_delay_model
        from repro.train.coded import CodedTrainer, TrainerConfig
        c, wl, tr = self.cfg, self.wl, self.cfg["trainer"]
        opt = c["optimizer"]
        seed32 = self.seed & 0x7FFFFFFF
        self.tcfg = TrainerConfig(
            m_workers=tr["m_workers"], beta=tr["beta"], wait_k=tr["wait_k"],
            rows_per_worker=tr["rows_per_worker"], seq_len=wl["seq_len"],
            steps=wl["max_steps"], lr=opt["lr"], warmup=opt["warmup"],
            seed=seed32, log_every=0, code=tr["code"])
        engine = ClusterEngine(make_delay_model(tr["delay"]), tr["m_workers"],
                               seed=seed32)
        self.trainer = CodedTrainer(self.arch(), self.tcfg, engine)
        self.tokens = SeededTokens(c["vocab_size"], self.seed,
                                   int(wl["check"]["steps"]))
        self.trainer.batcher = GroupBatcher(
            self.tokens, self.trainer.code, tr["rows_per_worker"],
            wl["seq_len"])
        self._check_layout(self.layout())

    def first_steps(self) -> None:
        """Weights from the seed, then the check's steps."""
        import jax
        import jax.numpy as jnp
        from repro.optim import adamw_init
        c, wl = self.cfg, self.wl
        opt = c["optimizer"]
        n_check = int(wl["check"]["steps"])
        lay = self.layout()
        params0 = ref.init(lay, self.seed, jnp.dtype(c["param_dtype"]))
        opt0 = adamw_init(params0, dtype=jnp.dtype(c["optstate_dtype"]))

        # the check's steps: one, then the rest, through the window's call;
        # the first weights wait on the host, so the device holds no more
        # than the step's own arguments and results
        host0 = jax.device_get(params0)
        losses = []
        cb = lambda rec: losses.append(rec["loss"])
        self.trainer.tcfg = dataclasses.replace(self.tcfg, steps=1)
        p, o, _ = self.trainer.run(params0, opt0, cb)
        del params0, opt0
        b1 = opt["b1"]
        self.prog_grad1 = {k: v / (1.0 - b1)
                           for k, v in ref.leaf_norms(o.m).items()}
        self.trainer.tcfg = dataclasses.replace(self.tcfg,
                                                steps=n_check - 1)
        p, o, _ = self.trainer.run(p, o, cb)
        self.prog_change = ref.host_change_norms(jax.device_get(p), host0)
        self.prog_loss = losses
        self.n_params = sum(int(np.prod(v[0])) for v in lay.values())
        self.state = (p, o)
        self.trainer.tcfg = self.tcfg

    def _check_layout(self, lay: dict) -> None:
        """The program's parameter tree must be the layout drawn here."""
        import jax
        from repro.models import transformer as T
        shapes = jax.eval_shape(lambda: T.init_params(
            self.trainer.cfg, jax.random.key(0)))
        got = {k: tuple(v.shape) for k, v in ref.flat_leaves(shapes).items()}
        want = {k: tuple(v[0]) for k, v in lay.items()}
        if got != want:
            raise ValueError(f"parameter layout {got} != {want}")

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> WindowRecord:
        times = []
        t0 = time.perf_counter()

        def cb(rec):
            now = time.perf_counter()
            times.append(now)
            if now - t0 >= seconds:
                raise _WindowClosed

        p, o = self.state
        self.state = None
        try:
            self.trainer.run(p, o, cb)
        except _WindowClosed:
            pass
        del p, o
        steps = len(times)
        window_s = times[-1] - t0
        tr = self.cfg["trainer"]
        groups = self.trainer.code.num_groups
        tokens = steps * groups * tr["rows_per_worker"] * self.wl["seq_len"]
        gaps = np.diff(np.asarray([t0] + times))
        return WindowRecord(
            attempted=steps, failed=0, window_s=window_s,
            e2e={"tokens_per_s": rate(tokens, window_s),
                 "step_p95_ms": p95(gaps) * 1e3},
            counts={"steps": steps, "tokens": tokens,
                    "step_s": gaps.tolist()})

    def release(self) -> None:
        self.trainer = None
        self.state = None
        gc.collect()

    # -- correctness ---------------------------------------------------------
    def reference(self, precision: str = "highest") -> dict:
        """The reference's check steps from the same weights and rows."""
        import jax.numpy as jnp
        params0 = ref.init(self.layout(), self.seed,
                           jnp.dtype(self.cfg["param_dtype"]))
        batches = [(t[:, :-1], t[:, 1:]) for t in self.tokens.kept]
        return ref.train(params0, batches, cfg=self.cfg,
                         total_steps=self.wl["max_steps"],
                         precision=precision)

    @staticmethod
    def compare(loss, grad1, change, want: dict) -> dict:
        keep = ref.moved_leaves(want["grad1"])
        return {
            "loss_rel_err": max(abs(a - b) / abs(b)
                                for a, b in zip(loss, want["loss"])),
            "grad1_norm_gap": ref.norm_gaps(grad1, want["grad1"], keep),
            "change_norm_gap": ref.norm_gaps(change, want["change"], keep),
        }

    def readings(self) -> dict:
        want = self.reference()
        return self.compare(self.prog_loss, self.prog_grad1,
                            self.prog_change, want)

    def calibrate(self) -> dict:
        """The program's readings and the control's (the reference in int8
        in the program's place)."""
        self.release()
        want = self.reference()
        ctl = self.reference("int8")
        return {"program": self.compare(self.prog_loss, self.prog_grad1,
                                        self.prog_change, want),
                "control": self.compare(ctl["loss"], ctl["grad1"],
                                        ctl["change"], want)}

    def check(self) -> list:
        got = self.readings()
        limits = self.wl["check"]["limits"]
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in got.items() if k in limits]
