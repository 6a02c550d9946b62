"""Fused masked-gradient path benchmark (DESIGN.md §12).

Two parts, one JSON:

* **kernel** — ``fused_masked_gradient`` (one Pallas call: residual
  matvec, erasure mask, decode-weighted combine, VMEM accumulator)
  against the dense three-``einsum`` reference, on the real encoded
  ridge-smoke operands plus a compare-scale shape.  Records mean us per
  call, the analytic FLOP count (4 m r p: two matvecs at 2 flops/MAC)
  and the ideal HBM byte traffic — ``benchmarks.roofline --fused`` turns
  these into achieved-vs-peak utilization.  On CPU the kernel runs in
  interpret mode (recorded as such; interpret timings measure the
  emulator, not the TPU dataflow).

* **matrix** — the paper's R=16 ridge matrix, device-resident: the ridge
  smoke problem (same data/shape as ``BENCH_experiments.json``'s cell)
  as a C=8-cell coded-gd matrix (2 delay models x 4 step-size variants),
  run through ``plan -> execute`` per-cell and with
  ``PlacementAxis(cell_batch=True)`` (one compiled program per
  compatible group).  Reports seconds/cell for both, the cell-batch
  speedup, the speedup over the recorded vmap baseline in
  ``BENCH_experiments.json``, and the max objective-trace difference
  between the two paths (must be <= 1e-4; in practice bit-identical).

Writes ``BENCH_fused.json`` at the repo root.

    PYTHONPATH=src python -m benchmarks.bench_fused            # full
    PYTHONPATH=src python -m benchmarks.bench_fused --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from .common import emit, time_us

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(_ROOT, "BENCH_fused.json")
BASELINE_JSON = os.path.join(_ROOT, "BENCH_experiments.json")


# ---------------------------------------------------------------------------
# Part A: the fused kernel vs the dense reference
# ---------------------------------------------------------------------------

def _dense_reference(SX, Sy, w, mask, *, n, beta):
    import jax.numpy as jnp
    k = jnp.maximum(mask.sum(), 1.0)
    c = mask * (SX.shape[0] / k) / (n * beta)
    u = jnp.einsum("mrp,p->mr", SX, w) - Sy
    return jnp.einsum("m,mrp,mr->p", c, SX, u).astype(w.dtype)


def _kernel_cases(smoke: bool):
    """(label, m, r, p) shapes; the first is the REAL encoded ridge smoke
    problem (built below), the rest synthetic at compare scale."""
    cases = [("ridge_smoke", None)]          # filled from the workload
    if not smoke:
        cases.append(("compare_m16", (16, 64, 128)))
    return cases


def _ridge_encoded():
    """The actual encoded operands of the ridge smoke cell."""
    from repro.core.data_parallel import make_encoded_problem
    from repro.runtime.strategies import _resolve_encoder
    from repro.workloads import get_workload

    data = get_workload("ridge").build("smoke")
    spec = data.spec
    m = 8
    enc = _resolve_encoder("hadamard", spec.n, beta=2.0, seed=0, m=m)
    prob = make_encoded_problem(spec.X, spec.y, enc, m, lam=spec.lam)
    return prob


def bench_kernel(smoke: bool, iters: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.fused_step import (fused_masked_gradient,
                                          pick_fused_block_rows)

    interpret = jax.default_backend() != "tpu"
    rows = []
    for label, shape in _kernel_cases(smoke):
        if shape is None:
            prob = _ridge_encoded()
            SX, Sy = prob.SX, prob.Sy
            n, beta = prob.n, prob.beta
        else:
            m_, r_, p_ = shape
            rng = np.random.default_rng(0)
            SX = jnp.asarray(rng.standard_normal((m_, r_, p_)), jnp.float32)
            Sy = jnp.asarray(rng.standard_normal((m_, r_)), jnp.float32)
            n, beta = m_ * r_ // 2, 2.0
        m, r, p = SX.shape
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal(p), jnp.float32)
        mask = jnp.asarray(rng.random(m) < 0.75, jnp.float32)

        fused = jax.jit(lambda SX, Sy, w, mask: fused_masked_gradient(
            SX, Sy, w, mask, n=n, beta=beta, interpret=interpret))
        dense = jax.jit(lambda SX, Sy, w, mask: _dense_reference(
            SX, Sy, w, mask, n=n, beta=beta))

        err = float(jnp.abs(fused(SX, Sy, w, mask)
                            - dense(SX, Sy, w, mask)).max())
        assert err <= 1e-4, f"fused kernel diverged: {err}"

        us_fused = time_us(fused, SX, Sy, w, mask, iters=iters)
        us_dense = time_us(dense, SX, Sy, w, mask, iters=iters)
        flops = 4 * m * r * p
        bytes_ideal = 4 * (m * r * p + m * r + p + p)
        mode = "interpret" if interpret else "compiled"
        emit(f"fused_kernel_{label}", us_fused,
             f"dense_us={us_dense:.1f};mode={mode};err={err:.2e}")
        rows.append({
            "case": label, "m": m, "r": r, "p": p,
            "block_rows": pick_fused_block_rows(r, p),
            "mode": mode,
            "us_fused": us_fused, "us_dense": us_dense,
            "flops": flops, "bytes_ideal": bytes_ideal,
            "max_abs_err": err,
        })
    return rows


# ---------------------------------------------------------------------------
# Part B: the R=16 ridge matrix, device-resident
# ---------------------------------------------------------------------------

def _matrix_spec(cell_batch: bool, trials: int, steps: int):
    from repro.experiments import (DelayAxis, ExperimentSpec, PlacementAxis,
                                   ProblemAxis, StrategyAxis, TrialsAxis)
    from repro.workloads import get_workload

    data = get_workload("ridge").build("smoke")
    strategies = tuple(
        StrategyAxis("coded-gd", k=6,
                     options=(() if s is None else (("step_size", s),)))
        for s in (None, 0.05, 0.02, 0.01))
    return ExperimentSpec(
        problems=(ProblemAxis.from_spec(data.spec),),
        strategies=strategies,
        delays=DelayAxis.of("bimodal", "power_law", m=8),
        trials=TrialsAxis(trials=trials),
        placement=PlacementAxis(mode="vmap", cell_batch=cell_batch),
        steps=steps)


def _time_matrix(spec, iters: int):
    """Best-of-``iters`` wall time for one warm ``execute`` of the matrix
    (min, not mean: the baseline in BENCH_experiments.json was recorded on
    an idle host, and min-of-N is the standard noise-robust estimator of
    that)."""
    from repro.experiments import execute, plan
    pl = plan(spec)
    # record_to=False: manifest writes (git subprocess + json) must not
    # land inside the timed loop or pollute the run store with warm-ups
    result = execute(pl, record_to=False)      # warm the jit caches
    secs = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        result = execute(pl, record_to=False)
        secs = min(secs, time.perf_counter() - t0)
    traces = np.stack([np.asarray(r["objective"], dtype=float)
                       for r in result.records])
    return secs, len(result.outcomes), traces


def bench_matrix(smoke: bool, iters: int) -> dict:
    import jax

    trials = 4 if smoke else 16
    steps = 40
    s_cell, C, tr_cell = _time_matrix(
        _matrix_spec(False, trials, steps), iters)
    s_batch, C2, tr_batch = _time_matrix(
        _matrix_spec(True, trials, steps), iters)
    assert C == C2
    trace_err = float(np.abs(tr_cell - tr_batch).max())
    assert trace_err <= 1e-4, f"cell-batched traces diverged: {trace_err}"

    baseline = None
    if os.path.exists(BASELINE_JSON):
        with open(BASELINE_JSON) as f:
            for row in json.load(f)["results"]:
                if row["placement"] == "vmap" and row["R"] == trials:
                    baseline = row["seconds_per_matrix"]
    speedup_batch = s_cell / max(s_batch, 1e-12)
    speedup_vs_baseline = (baseline / max(s_batch / C, 1e-12)
                           if baseline else None)
    derived = (f"percell_us={s_cell / C * 1e6:.1f};"
               f"cellbatch_speedup={speedup_batch:.2f}x")
    if speedup_vs_baseline:
        derived += f";vs_experiments_vmap={speedup_vs_baseline:.2f}x"
    emit(f"fused_matrix_R{trials}", s_batch / C * 1e6, derived)
    return {
        "R": trials, "steps": steps, "cells": C,
        "backend": jax.default_backend(),
        "seconds_per_cell_percell": s_cell / C,
        "seconds_per_cell_cellbatched": s_batch / C,
        "cellbatch_speedup": speedup_batch,
        "baseline_vmap_seconds_per_cell": baseline,
        "speedup_vs_experiments_vmap": speedup_vs_baseline,
        "max_abs_trace_err": trace_err,
    }


def run(smoke: bool = False, iters: int = 3,
        out_json: str = DEFAULT_OUT) -> dict:
    import jax
    from repro.kernels.fused_step import fused_enabled

    from .common import bench_meta

    kernel = bench_kernel(smoke, iters=max(iters, 3))
    matrix = bench_matrix(smoke, iters=iters)
    out = {
        "bench": "fused masked-gradient path (kernel + R=16 ridge matrix)",
        "meta": bench_meta(),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "fused_runner_path": fused_enabled(),
        "devices": len(jax.devices()),
        "kernel": kernel,
        "matrix": matrix,
    }
    with open(out_json, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {out_json}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.bench_fused")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: R=4, one kernel case")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    run(smoke=args.smoke, iters=args.iters, out_json=args.out)


if __name__ == "__main__":
    main()
