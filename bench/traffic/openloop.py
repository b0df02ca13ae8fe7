"""Open-loop request schedules on the wall clock, from a traffic file.

Every seed gets the same schedule of sizes and arrival gaps, position by
position: the gaps are the quantiles of an exponential distribution at
the file's rate (Poisson arrivals), the prompt and output lengths the
quantiles of log-uniform distributions over the file's bands, each put
in an order fixed by the stream alone.  The seed draws only the token
ids.  So two seeds offer the same work at the same times, and a
difference between their runs is the system's, not the draw's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float            # seconds after the schedule's start
    prompt: np.ndarray      # int32 token ids
    output_len: int


def _log_uniform_quantiles(n: int, lo: int, hi: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _exponential_quantiles(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def schedule(traffic: dict, seed: int, seconds: float, vocab_size: int,
             *, stream: int = 0) -> list:
    """Arrivals due in [0, seconds) at ``traffic["rate_per_s"]``.

    stream separates independent schedules (the warm-up and the measured
    window); it alone fixes the order of gaps and lengths."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(np.random.SeedSequence([stream, n]))
    gaps = order.permutation(_exponential_quantiles(n, rate))
    due = np.cumsum(gaps) - gaps[0] / 2
    due *= seconds / max(seconds, float(np.sum(gaps)))
    plens = order.permutation(_log_uniform_quantiles(n, *traffic["prompt_len"]))
    olens = order.permutation(_log_uniform_quantiles(n, *traffic["output_len"]))
    ids = np.random.default_rng(np.random.SeedSequence([seed, stream]))
    return [Arrival(float(t), ids.integers(0, vocab_size, int(p), dtype=np.int32),
                    int(o))
            for t, p, o in zip(due, plens, olens)]
