"""The measured window: what every driver hands back, and its arithmetic.

Rates are all the work completed in the window over the window's seconds;
tails are percentiles over every operation in the window, never a median
of chunks.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class WindowRecord:
    attempted: int
    failed: int
    window_s: float                  # first start to last completion
    e2e: dict                        # end-to-end metric name -> value
    counts: dict = dataclasses.field(default_factory=dict)


def p95(values) -> float:
    """95th percentile (linear interpolation) of every value given."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def rate(work: float, seconds: float) -> float:
    return float(work) / float(seconds)


class Deadline:
    """Closed loop: the next operation starts when the last has ended, and
    none starts once ``seconds`` have passed since the window opened."""

    def __init__(self, seconds: float):
        self.t0 = time.perf_counter()
        self.seconds = float(seconds)

    def open(self) -> bool:
        return time.perf_counter() - self.t0 < self.seconds

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0
