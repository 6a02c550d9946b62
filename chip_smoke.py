#!/usr/bin/env python3
"""Smoke run of the coded-optimization path on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: phase C only

Phase A is the paper's main path: the ridge workload at its published
Fig-7 shape (``paper`` preset, n=4096, p=6000, m=32, k=24), bimodal
delays, 4 vmapped trials of 100 steps, for coded-gd with the fast-Hadamard
encoder (the FWHT encode kernels and the fused masked-gradient kernel),
coded-lbfgs (the masked-combine kernel at p=6000) and uncoded.  It checks
the records, a masked gradient of each kernel against a float32 reference,
the encoder's worker blocks against its materialized matrix, and that the
coded-gd runner compiled its kernel for the chip.

Phase B trains the 100m coded LM (deepseek-7b family) with coded-sgd and
uncoded for 3 steps.

Phase C (``--chips 4``) runs ridge ``paper`` coded-gd with 8 trials, once
sharded over the four-chip trials mesh and once vmapped on one chip, and
compares the per-realization traces.

Everything runs in this one process and goes through the normal
``spec -> plan -> execute`` entry points.  Seconds printed here are smoke
numbers, not benchmark numbers.  The last line of standard output is one
JSON object; it reports ``"ok": true`` only when every phase passed, and
nothing is reported where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
REL_TOL = 1e-4


def _rel_err(got, want) -> float:
    """max |got - want| / max |want|."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def _ridge_spec(strategies, *, preset: str, trials: int, placement: str):
    from repro.experiments.spec import (DelayAxis, ExperimentSpec,
                                        PlacementAxis, ProblemAxis,
                                        TrialsAxis)
    return ExperimentSpec(
        problems=(ProblemAxis.from_workload("ridge", preset),),
        strategies=tuple(strategies),
        delays=DelayAxis(delays=("bimodal",)),
        trials=TrialsAxis(trials=trials, eval_every=1, seed=0),
        placement=PlacementAxis(mode=placement))


def _execute(spec):
    from repro.experiments.execute import execute
    from repro.experiments.plan import plan
    result = execute(plan(spec), record_to=False)
    for rec in result.records:
        _check("skipped" not in rec,
               f"{rec['strategy']} cell ran (not skipped)")
    return result


def phase_a(preset: str = "paper") -> None:
    """The paper's main path, its kernels checked against references."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.data_parallel import (_masked_mean, encoded_gradients,
                                          make_encoded_problem)
    from repro.core.encoding import make_encoder
    from repro.experiments.spec import StrategyAxis
    from repro.kernels.fused_step import fused_masked_gradient
    from repro.runtime import runners
    from repro.workloads import get_workload

    result = _execute(_ridge_spec(
        (StrategyAxis("coded-gd", encoder="fast-hadamard"),
         StrategyAxis("coded-lbfgs"), StrategyAxis("uncoded")),
        preset=preset, trials=4, placement="vmap"))
    for rec in result.records:
        obj = np.asarray(rec["objective"], np.float64)
        _check(np.isfinite(obj).all(),
               f"{rec['strategy']}: all {obj.size} objectives finite")
        if rec["strategy"].startswith("coded"):
            gap = np.asarray(rec["metric"], np.float64)      # (trials, T)
            _check(bool((gap[:, -1] < gap[:, 0]).all()),
                   f"{rec['strategy']}: final subopt gap "
                   f"{gap[:, -1].max():.3e} below first {gap[:, 0].min():.3e}")

    wl = get_workload("ridge")
    ps = wl.preset(preset)
    spec = wl.build(ps).spec
    enc = make_encoder("fast-hadamard", spec.n, beta=2.0, seed=0)
    enc = enc.with_workers(ps.m)
    prob = make_encoded_problem(spec.X, spec.y, enc, ps.m, lam=spec.lam)

    Xy = np.concatenate([spec.X, spec.y[:, None]], axis=1)
    want = enc.materialize() @ Xy                          # (rows, p + 1)
    got = np.concatenate([np.asarray(prob.SX), np.asarray(prob.Sy)[..., None]],
                         axis=-1).reshape(want.shape)
    err = _rel_err(got, want)
    _check(err < REL_TOL, f"fast-hadamard encode == materialize() "
                          f"(rel err {err:.2e})")
    r = enc.rows_per_worker
    for i in (0, ps.m - 1):
        err = _rel_err(enc.worker_block(i, Xy), want[i * r:(i + 1) * r])
        _check(err < REL_TOL, f"fast-hadamard worker_block({i}) == "
                              f"materialize() (rel err {err:.2e})")

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(spec.p), jnp.float32)
    mask = np.zeros(ps.m, np.float32)
    mask[rng.choice(ps.m, ps.k, replace=False)] = 1.0
    mask = jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        G = encoded_gradients(prob, w)                     # (m, p)
        want = jnp.einsum("m,mp->p", mask * (ps.m / ps.k), G)
    fused = fused_masked_gradient(prob.SX, prob.Sy, w, mask, n=prob.n,
                                  beta=prob.beta)
    err = _rel_err(fused, want)
    _check(err < REL_TOL, f"fused masked gradient == f32 reference "
                          f"(rel err {err:.2e})")
    err = _rel_err(_masked_mean(G, mask), want)
    _check(err < REL_TOL, f"masked-combine kernel == f32 reference "
                          f"(rel err {err:.2e})")

    combine_hlo = jax.jit(_masked_mean).lower(G, mask).compile().as_text()
    _check("tpu_custom_call" in combine_hlo,
           "masked combine compiled with its Pallas kernel")
    masks = jnp.zeros((4, ps.steps, ps.m), jnp.float32)
    w0 = jnp.zeros((4, spec.p), jnp.float32)
    runner_hlo = runners._batched_scan_gd.lower(
        prob, masks, 1e-3, w0, h="l2", eval_every=1,
        degrade=None).compile().as_text()
    _check("tpu_custom_call" in runner_hlo,
           "coded-gd runner compiled with its Pallas kernel")


def phase_b(preset: str = "100m", m: int = 8) -> None:
    """Coded LM training for a few steps."""
    import numpy as np
    from repro.experiments.spec import (DelayAxis, ExperimentSpec,
                                        PlacementAxis, ProblemAxis,
                                        StrategyAxis)
    print(f"  coded LM at m={m} workers (the step fits one v5e at m=8)",
          flush=True)
    result = _execute(ExperimentSpec(
        problems=(ProblemAxis.train("deepseek-7b", preset=preset,
                                    seq_len=64),),
        strategies=(StrategyAxis("coded-sgd"), StrategyAxis("uncoded")),
        delays=DelayAxis(delays=("bimodal",), m=m),
        placement=PlacementAxis(mode="single"), steps=3))
    for rec in result.records:
        loss = np.asarray(rec["objective"], np.float64)
        _check(loss.size == 3 and np.isfinite(loss).all(),
               f"{rec['strategy']}: losses finite {loss.tolist()}")


def phase_c(preset: str = "paper", chips: int = 4) -> None:
    """Trials sharded over the chips vs vmapped on one."""
    import numpy as np
    from repro.experiments.spec import StrategyAxis
    recs = {}
    for placement in ("sharded", "vmap"):
        result = _execute(_ridge_spec(
            (StrategyAxis("coded-gd", encoder="fast-hadamard"),),
            preset=preset, trials=8, placement=placement))
        recs[placement] = result.records[0]
    ndev = recs["sharded"]["meta"].get("placement_devices")
    _check(ndev == chips, f"sharded placement used {ndev} devices")
    sharded = np.asarray(recs["sharded"]["objective"], np.float64)
    vmapped = np.asarray(recs["vmap"]["objective"], np.float64)
    _check(sharded.shape == vmapped.shape == (8, sharded.shape[1]),
           f"8 per-realization traces of {sharded.shape[1]} steps")
    err = float(np.abs(sharded - vmapped).max())
    _check(np.allclose(sharded, vmapped, rtol=1e-5, atol=1e-5),
           f"sharded traces == vmap traces (max abs diff {err:.2e})")


def _run_phase(name: str, fn) -> bool:
    from repro.obs.timing import CompileWatch
    print(f"phase {name}", flush=True)
    try:
        with CompileWatch() as cw:
            fn()
    except Exception:                            # noqa: BLE001
        traceback.print_exc()
        print(f"phase {name}: FAILED", flush=True)
        return False
    print(f"phase {name}: passed (smoke: wall {cw.total_s:.1f} s, compile "
          f"{cw.compile_s:.1f} s in {cw.compiles} compiles)", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B on one chip; 4: phase C only")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); the "
              f"CPU path is not a substitute", file=sys.stderr)
        return 1
    print(f"platform {platform}, device_kind {kind}, devices "
          f"{len(devices)}, compile cache {cache}", flush=True)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [("C (sharded trials on 4 chips)", phase_c)]
    else:
        phases = [("A (ridge paper: coded-gd, coded-lbfgs, uncoded)",
                   phase_a),
                  ("B (coded LM 100m: coded-sgd, uncoded)", phase_b)]
    ok = all([_run_phase(name, fn) for name, fn in phases])
    print(f"smoke: total wall {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
