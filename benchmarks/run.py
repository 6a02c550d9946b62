"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Roofline terms come from the
dry-run artifacts (launch/dryrun.py writes JSON; benchmarks/roofline.py
renders the table) since they require the 512-device process.

Every invocation also records one ``kind="bench"`` manifest (suite list,
per-suite seconds, failures, provenance) into the run store — disable via
``REPRO_RUNSTORE=0`` (see ``repro.obs.runstore``).
"""
from __future__ import annotations

import sys
import time


def _record_suite_manifest(suite_rows: list, total_s: float) -> None:
    """Best-effort run-store manifest for the whole suite invocation."""
    try:
        from repro.obs.runstore import default_store
        store = default_store()
        if store is None:
            return
        run_id = store.record({
            "kind": "bench",
            "label": "benchmarks.run suite",
            "suites": suite_rows,
            "total_s": total_s,
        })
        print(f"# recorded bench run {run_id} in {store.root}")
    except Exception as e:  # noqa: BLE001
        print(f"# runstore: suite manifest not recorded: {e}")


def main() -> int:
    """Run every suite; returns 1 when any suite failed (after running the
    rest), else 0."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_spectrum, bench_ridge, bench_lasso, bench_logistic,
                   bench_matrix_factorization, bench_kernels, bench_coded_lm,
                   bench_runtime, bench_encoding, bench_trials,
                   bench_experiments, bench_fused, bench_faults, perf_iter)
    print("name,us_per_call,derived")
    suites = [
        ("spectrum (paper Figs 5-6)", bench_spectrum.run),
        ("encoding operators (matrix-free, DESIGN §7)", bench_encoding.run),
        ("ridge L-BFGS (paper Fig 7)", bench_ridge.run),
        ("lasso proximal (paper Fig 14)", bench_lasso.run),
        ("logistic BCD (paper Figs 10-13)", bench_logistic.run),
        ("matrix factorization (paper Tables 2-3)",
         bench_matrix_factorization.run),
        ("coded-DP LM trainer (beyond-paper, DESIGN §4)", bench_coded_lm.run),
        ("kernels", bench_kernels.run),
        ("runtime scan-fused vs legacy loops", bench_runtime.run),
        ("batched trials vs sequential loop (DESIGN §9)", bench_trials.run),
        ("experiment placement axis single/vmap/sharded (DESIGN §10)",
         bench_experiments.run),
        ("fused masked-gradient path: kernel + cell-batched matrix "
         "(DESIGN §12)", bench_fused.run),
        ("fault-injection overhead: no-fault path + chaos cells "
         "(DESIGN §14)", bench_faults.run),
        ("perf-iter roofline dry-run (512-device subprocess)",
         perf_iter.run),
    ]
    t_all = time.time()
    suite_rows = []
    for title, fn in suites:
        print(f"# --- {title} ---", flush=True)
        t0 = time.time()
        status = "ok"
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            print(f"{title.split()[0]}_FAILED,0.0,{e!r}", flush=True)
            import traceback
            traceback.print_exc()
            status = f"failed: {e!r}"
        secs = time.time() - t0
        suite_rows.append({"suite": title, "seconds": secs,
                           "status": status})
        print(f"# ({title}: {secs:.1f}s)", flush=True)
    total_s = time.time() - t_all
    print(f"# total: {total_s:.1f}s")
    _record_suite_manifest(suite_rows, total_s)
    return int(any(r["status"] != "ok" for r in suite_rows))


if __name__ == "__main__":
    sys.exit(main())
