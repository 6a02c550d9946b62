#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--out chiprun_out/calibrate-<cell>.json]

For each seed, in one process: the cell's set-up, then the program's
compared numbers against the plain reference and the control's (the
reference in the next lower precision in the program's place), as the
driver's ``calibrate()`` defines them; for a training cell also those of
runs with a fault planted in the feed (``FAULTS``).  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]

import numpy as np  # noqa: E402

from chipbench import run  # noqa: E402


class _HalfBatch:
    """The batcher's combine coefficients drop the second half of the data
    groups and double the first: the mean over the rest."""

    def __init__(self, batcher, code):
        self.batcher = batcher
        keep = np.asarray(code.worker_groups)[:, 0] < code.num_groups // 2
        self.scale = np.where(keep, 2.0, 0.0).astype(np.float32)[:, None]

    def next_batch(self, code=None):
        tokens, labels, coeff = self.batcher.next_batch(code)
        return tokens, labels, coeff * self.scale


class _AlteredTokens:
    """One id changed where it is produced, in each row that the
    reference also trains on (after the row was kept for it)."""

    def __init__(self, tokens):
        self.tokens = tokens
        self.vocab = tokens.vocab

    def sample(self, rng, n: int, seq: int):
        kept = len(self.tokens.kept)
        toks = self.tokens.sample(rng, n, seq)
        if len(self.tokens.kept) > kept:
            toks[0, seq // 2] = (toks[0, seq // 2] + 1) % self.vocab
        return toks


def _plant_half(session) -> None:
    session.trainer.batcher = _HalfBatch(session.trainer.batcher,
                                         session.trainer.code)


def _plant_token(session) -> None:
    session.trainer.batcher.stream = _AlteredTokens(session.tokens)


# faults planted in a training cell's feed, by driver
FAULTS = {"train": {"half": _plant_half, "token": _plant_token}}


def fault_readings(driver, cfg, wl, seed, devices) -> dict:
    """The compared numbers of a run with each of the driver's faults
    planted before its first steps."""
    out = {}
    for name, plant in FAULTS.get(wl["driver"], {}).items():
        s = driver.Session(cfg, wl, seed, devices)
        s.build()
        plant(s)
        s.first_steps()
        s.release()
        out["fault_" + name] = s.readings()
        del s
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import importlib
    run.setup_jax()
    _, entry, wl, cfg = run.load_cell(args.workload)
    devices = run.require_chips(int(entry["chips"]))
    driver = importlib.import_module("chipbench.traffic." + wl["driver"])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        session = driver.Session(cfg, wl, seed, devices)
        session.setup()
        got = session.calibrate()
        del session
        got.update(fault_readings(driver, cfg, wl, seed, devices))
        got.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(got), flush=True)
        rows.append(got)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
