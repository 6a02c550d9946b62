"""Driver for convex solves: closed-loop ``Strategy.run_batched`` calls.

Set-up makes the data from the seed on the device (one jitted call),
computes the step size, and runs one whole solve so that every program the
window uses is compiled.  The window then calls ``run_batched`` on the
``ProblemSpec`` built in set-up, one solve after the other, each with a
cluster engine of its own seed, so every solve draws its own (R, T, m)
straggler schedule.  A solve is timed from the call to the host holding
its iterates and objective trace: encode, schedule draw, scan and
read-back.  After the window a sample of its solves, drawn from the seed,
is recomputed by ``chipbench.reference.ridge`` from the same data and the
schedules the program drew, which are first held to the cell's law.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.window import Deadline, WindowRecord, p95, rate


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_data(seed: int, n: int, p: int, noise: float, device=None):
    """X (n, p) and y (n,) as float32 host arrays, drawn on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kx, kw, ke = jax.random.split(key, 3)
        X = jax.random.normal(kx, (n, p), jnp.float32)
        w0 = jax.random.normal(kw, (p,), jnp.float32)
        y = (jnp.dot(X, w0, precision=jax.lax.Precision.HIGHEST)
             + noise * jax.random.normal(ke, (n,), jnp.float32))
        return X, y

    key = _key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    X, y = gen(key)
    return np.asarray(X), np.asarray(y)


def lipschitz(X: np.ndarray, iters: int = 100) -> float:
    """Largest eigenvalue of X^T X / n by power iteration on the device."""
    import jax
    import jax.numpy as jnp
    n, p = X.shape
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def power(X):
        def body(_, v):
            u = jnp.dot(X.T, jnp.dot(X, v, precision=hi), precision=hi) / n
            return u / jnp.linalg.norm(u)
        v = jax.lax.fori_loop(0, iters, body,
                              jnp.ones((p,), jnp.float32) / np.sqrt(p))
        return jnp.vdot(v, jnp.dot(X.T, jnp.dot(X, v, precision=hi),
                                   precision=hi)) / n

    return float(power(jnp.asarray(X)))


def engine_seed(seed: int, index: int) -> int:
    """The cluster seed of solve ``index`` of the run with ``seed``."""
    return int(np.random.SeedSequence([seed, 1, index + 1])
               .generate_state(1)[0])


class Session:
    def __init__(self, cfg: dict, wl: dict, seed: int, devices):
        self.cfg, self.wl, self.seed = cfg, wl, int(seed)
        self.devices = devices
        self.outputs: list = []
        self._encoded = None

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from repro.runtime.strategies import ProblemSpec, get_strategy
        c = self.cfg
        self.X, self.y = make_data(self.seed, c["n"], c["p"], c["noise"],
                                   self.devices[0])
        self.spec = ProblemSpec(X=self.X, y=self.y, lam=c["lam"], h="l2")
        self.step_size = 1.0 / (1.3 * lipschitz(self.X) + c["lam"])
        N = 1 << max(0, int(round(c["beta"] * c["n"])) - 1).bit_length()
        self.rows_per_worker = -(-N // c["m"])
        self.strategy = get_strategy(self.wl["strategy"])
        self.solve(-1)                    # compiles every program used

    def solve(self, index: int) -> dict:
        """One ``run_batched`` call; returns what the check needs."""
        from repro.runtime.engine import ClusterEngine, make_delay_model
        c, wl = self.cfg, self.wl
        engine = ClusterEngine(make_delay_model(c["delay"]), c["m"],
                               seed=engine_seed(self.seed, index))
        res = self.strategy.run_batched(
            self.spec, engine, steps=c["steps"], trials=wl["trials"],
            eval_every=wl["eval_every"], placement=wl["placement"],
            k=wl["k"], encoder=wl["encoder"], beta=c["beta"],
            encoder_seed=wl["encoder_seed"], step_size=self.step_size)
        return {"index": index, "masks": np.asarray(res.schedules.masks),
                "w": np.asarray(res.w),
                "objective": np.asarray(res.objective)}

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> WindowRecord:
        dl = Deadline(seconds)
        lat = []
        i = 0
        while dl.open():
            t0 = time.perf_counter()
            self.outputs.append(self.solve(i))
            lat.append(time.perf_counter() - t0)
            i += 1
        window_s = dl.elapsed()
        R, T = self.wl["trials"], self.cfg["steps"]
        iters = i * R * T
        return WindowRecord(
            attempted=i, failed=0, window_s=window_s,
            e2e={"iters_per_s": rate(iters, window_s),
                 "solve_p95_ms": p95(lat) * 1e3},
            counts={"solves": i, "realization_iters": iters,
                    "latencies_s": lat})

    def release(self) -> None:
        self.strategy = None
        self.spec = None
        gc.collect()

    # -- correctness ---------------------------------------------------------
    def sample(self) -> list:
        """The window's solves that the check recomputes, drawn from the
        seed."""
        k = min(int(self.wl["check"]["solves"]), len(self.outputs))
        rng = np.random.default_rng([self.seed, 2])
        idx = sorted(rng.choice(len(self.outputs), size=k, replace=False))
        return [self.outputs[i] for i in idx]

    def reference(self, masks, precision: str = "highest"):
        """(w (R, p), objective (R, T)) of the plain reference on the
        set-up's data and the given schedules."""
        from chipbench.reference import ridge as ref
        c, wl = self.cfg, self.wl
        if self._encoded is None:
            self._encoded = ref.encode(self.X, self.y, beta=c["beta"],
                                       m=c["m"], seed=wl["encoder_seed"])
        SX, Sy = self._encoded
        return ref.gd(SX, Sy, self.X, self.y, masks, self.step_size,
                      lam=c["lam"], beta=c["beta"], precision=precision)

    def schedule_faults(self, masks) -> float:
        """How often the schedules the program drew, which the reference
        follows, break the cell's law: a shape other than (R, T, m), an
        entry other than 0 or 1, an iteration without exactly k active
        workers, a realization that repeats another."""
        R, T, m = self.wl["trials"], self.cfg["steps"], self.cfg["m"]
        masks = np.asarray(masks)
        if masks.shape != (R, T, m):
            return 1.0
        bad = np.sum((masks != 0) & (masks != 1))
        bad += np.sum(masks.sum(-1) != self.wl["k"])
        bad += R - len(np.unique(masks.reshape(R, -1), axis=0))
        return float(bad)

    @staticmethod
    def compare(w, f, w_ref, f_ref) -> dict:
        """The worst realization's relative error of the final iterate,
        and the worst relative error of the objective trace over every
        realization and iteration."""
        from chipbench.reference.ridge import rel_err_rows
        return {"w_rel_err": rel_err_rows(w, w_ref),
                "objective_rel_err": float(np.max(
                    np.abs(np.asarray(f, np.float64) - f_ref)
                    / np.abs(f_ref)))}

    def readings(self, outputs: list) -> dict:
        """The compared numbers of ``outputs``: the worst of each over
        them, and their schedule faults summed."""
        worst = {"schedule_faults": 0.0}
        for out in outputs:
            worst["schedule_faults"] += self.schedule_faults(out["masks"])
            w_ref, f_ref = self.reference(out["masks"])
            got = self.compare(out["w"], out["objective"], w_ref, f_ref)
            for k, v in got.items():
                worst[k] = max(worst.get(k, 0.0), v)
        return worst

    def calibrate(self) -> dict:
        """Readings for setting the limits, on one solve of this seed: the
        program's; the control's (the reference with every product's
        operands rounded to int8, one step below the configuration's
        bfloat16 products, in the program's place); and, beside them, the
        reference at the configuration's own precision (bfloat16
        operands), which a sound program may match."""
        out = self.solve(0)
        w_ref, f_ref = self.reference(out["masks"])
        got = {"program": {
            **self.compare(out["w"], out["objective"], w_ref, f_ref),
            "schedule_faults": self.schedule_faults(out["masks"])}}
        for name, prec in (("control", "int8"), ("stated", "bf16")):
            w, f = self.reference(out["masks"], prec)
            got[name] = self.compare(w, f, w_ref, f_ref)
        return got

    def check(self) -> list:
        if not self.outputs:
            return [{"name": "solves_checked", "value": 1.0, "limit": 0.0}]
        got = self.readings(self.sample())
        limits = self.wl["check"]["limits"]
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in got.items() if k in limits]
