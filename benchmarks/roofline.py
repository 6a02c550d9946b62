"""Render the §Roofline table from dry-run JSON artifacts, and the
achieved-vs-peak table for the fused masked-gradient path.

  PYTHONPATH=src python -m benchmarks.roofline --dir runs/dryrun [--md]
  PYTHONPATH=src python -m benchmarks.roofline --fused BENCH_fused.json

The first form reads every <arch>__<shape>__<mesh>.json emitted by
repro.launch.dryrun and prints the three roofline terms, dominant
bottleneck, MODEL_FLOPS ratio and memory footprint per combo.

The second reads ``benchmarks.bench_fused``'s kernel records (measured us
per call + analytic FLOPs and ideal HBM bytes) and prints achieved
GFLOP/s and GB/s against the published peaks of the device the records
name (``device_kind``), plus the implied arithmetic intensity and the
roofline-predicted bound.  A device with no published peaks is an error,
not a default: records from a CPU run have none, and ``--peak-gflops`` /
``--peak-gbps`` must then both be given explicitly.
"""
from __future__ import annotations

import argparse
import json
import os

# Published peaks of one chip, keyed by JAX's ``device_kind``.  TPU v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"gflops": 197e3, "gbps": 819.0},
}


def device_peaks(device_kind: str | None) -> dict:
    """The published peaks of ``device_kind``; raises for any other."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]


def fused_table(path: str, *, peak_gflops: float | None = None,
                peak_gbps: float | None = None, md: bool = False) -> None:
    """Achieved-vs-peak rows for every kernel case in BENCH_fused.json."""
    with open(path) as f:
        data = json.load(f)
    kind = data.get("device_kind")
    if peak_gflops and peak_gbps:
        pg, pb = peak_gflops, peak_gbps
    else:
        peaks = device_peaks(kind)
        pg = peak_gflops or peaks["gflops"]
        pb = peak_gbps or peaks["gbps"]
    hdr = ["case", "mode", "m", "r", "p", "us", "GFLOP/s", "%peak",
           "GB/s", "%peak_bw", "intensity", "bound"]
    sep = " | " if md else ","
    if md:
        print("| " + " | ".join(hdr) + " |")
        print("|" + "---|" * len(hdr))
    else:
        print(sep.join(hdr))
    for r in data.get("kernel", []):
        s = r["us_fused"] * 1e-6
        gflops = r["flops"] / s / 1e9
        gbps = r["bytes_ideal"] / s / 1e9
        intensity = r["flops"] / r["bytes_ideal"]
        bound = "compute" if intensity > pg / pb else "memory"
        cells = [r["case"], r["mode"], r["m"], r["r"], r["p"],
                 f"{r['us_fused']:.1f}", f"{gflops:.2f}",
                 f"{100 * gflops / pg:.2f}%", f"{gbps:.2f}",
                 f"{100 * gbps / pb:.2f}%", f"{intensity:.1f}", bound]
        line = sep.join(str(c) for c in cells)
        print(("| " + line + " |") if md else line)
    note = f"device_kind={kind} peaks: {pg:.0f} GFLOP/s, {pb:.0f} GB/s"
    print(f"{'<!-- ' if md else '# '}{note}{' -->' if md else ''}")


def load_records(d: str):
    recs = []
    for f in sorted(os.listdir(d)):
        if f.endswith(".json"):
            with open(os.path.join(d, f)) as fh:
                recs.append(json.load(fh))
    return recs


def fmt_row(r: dict, md: bool = False):
    if "error" in r:
        cells = [r["arch"], r["shape"], r.get("mesh", "?"), "ERROR",
                 r["error"][:40], "", "", "", ""]
    else:
        rl = r["roofline"]
        mem_gb = (r["memory"]["argument_bytes_per_device"]
                  + r["memory"]["temp_bytes_per_device"]) / 2 ** 30
        cells = [
            r["arch"], r["shape"], r["mesh"],
            f"{rl['compute_s'] * 1e3:.2f}", f"{rl['memory_s'] * 1e3:.2f}",
            f"{rl['collective_s'] * 1e3:.2f}", rl["bottleneck"],
            f"{rl.get('useful_ratio', 0):.3f}", f"{mem_gb:.1f}",
        ]
    sep = " | " if md else ","
    line = sep.join(str(c) for c in cells)
    return ("| " + line + " |") if md else line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="runs/dryrun")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--fused", default=None, metavar="BENCH_fused.json",
                    help="print achieved-vs-peak for the fused kernel "
                         "records instead of the dry-run table")
    ap.add_argument("--peak-gflops", type=float, default=None)
    ap.add_argument("--peak-gbps", type=float, default=None)
    args = ap.parse_args()
    if args.fused:
        fused_table(args.fused, peak_gflops=args.peak_gflops,
                    peak_gbps=args.peak_gbps, md=args.md)
        return
    recs = load_records(args.dir)
    hdr = ["arch", "shape", "mesh", "compute_ms", "memory_ms",
           "collective_ms", "bottleneck", "useful_ratio", "mem_GB/dev"]
    if args.md:
        print("| " + " | ".join(hdr) + " |")
        print("|" + "---|" * len(hdr))
    else:
        print(",".join(hdr))
    for r in recs:
        print(fmt_row(r, args.md))
    ok = sum(1 for r in recs if "error" not in r)
    print(f"{'<!-- ' if args.md else '# '}{ok}/{len(recs)} combos lowered "
          f"and compiled{' -->' if args.md else ''}")


if __name__ == "__main__":
    main()
