"""§Perf hillclimb driver: run one (arch, shape) dry-run with config
overrides and print the roofline terms compactly (+ hotspots on demand).

  PYTHONPATH=src python -m benchmarks.perf_iter --arch starcoder2-3b \
      --shape prefill_32k --override '{"seq_parallel_attn": true}' --hotspots

Appends one CSV row per invocation to runs/perf_log.csv.

The dry-run needs the 512-device host platform, which must be configured
before jax initializes — so ``main()`` sets ``XLA_FLAGS`` (and the
``benchmarks.run`` suite invokes this module as a subprocess rather than
in-process, where jax is long since initialized with the real device
count).
"""
import argparse
import csv
import json
import os

XLA_DEVICE_FLAGS = "--xla_force_host_platform_device_count=512"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--override", default="{}")
    ap.add_argument("--tag", default="")
    ap.add_argument("--hotspots", action="store_true")
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--log", default="runs/perf_log.csv")
    args = ap.parse_args(argv)

    # must land before the first jax import touches the backend
    os.environ["XLA_FLAGS"] = XLA_DEVICE_FLAGS

    from repro.launch.dryrun import dryrun_one
    rec = dryrun_one(args.arch, args.shape, args.multi, verbose=False,
                     extra_overrides=json.loads(args.override),
                     hotspots=args.hotspots)
    rl = rec["roofline"]
    row = {
        "tag": args.tag or args.override,
        "arch": args.arch, "shape": args.shape,
        "compute_s": round(rl["compute_s"], 3),
        "memory_s": round(rl["memory_s"], 3),
        "collective_s": round(rl["collective_s"], 3),
        "bottleneck": rl["bottleneck"],
        "useful_ratio": round(rl.get("useful_ratio", 0), 4),
        "allgather_GB": round(rec["collectives"]["all-gather"] / 1e9, 1),
        "allreduce_GB": round(rec["collectives"]["all-reduce"] / 1e9, 1),
        "a2a_GB": round(rec["collectives"]["all-to-all"] / 1e9, 1),
        "permute_GB": round(
            rec["collectives"]["collective-permute"] / 1e9, 1),
        "compile_s": rec["compile_s"],
    }
    print(json.dumps(row, indent=1))
    os.makedirs(os.path.dirname(args.log), exist_ok=True)
    exists = os.path.exists(args.log)
    with open(args.log, "a", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(row))
        if not exists:
            w.writeheader()
        w.writerow(row)
    return row


def run() -> None:
    """The ``benchmarks.run`` suite entry: one smoke (arch, shape) dry-run
    in a subprocess (the 512-device XLA flag cannot be applied to an
    already-initialized in-process jax), emitted in the suite's
    ``name,us_per_call,derived`` CSV convention."""
    import subprocess
    import sys
    import tempfile
    import time

    from .common import emit

    with tempfile.TemporaryDirectory() as td:
        log = os.path.join(td, "perf_log.csv")
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.perf_iter",
             "--arch", "xlstm-350m", "--shape", "train_4k",
             "--tag", "suite-smoke", "--log", log],
            capture_output=True, text=True, timeout=900,
            # a CPU rehearsal: the child must never compete with this
            # process for the accelerator
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": os.pathsep.join(
                     p for p in (os.environ.get("PYTHONPATH"), "src") if p)})
        wall_us = (time.perf_counter() - t0) * 1e6
        if proc.returncode != 0:
            raise RuntimeError(
                f"perf_iter subprocess failed:\n{proc.stderr[-2000:]}")
        out = proc.stdout
        row = json.loads(out[out.index("{"):])
    emit("perf_iter_xlstm350m_train4k", wall_us,
         f"bottleneck={row['bottleneck']};compute_s={row['compute_s']};"
         f"useful_ratio={row['useful_ratio']}")


if __name__ == "__main__":
    main()
