"""Clock and percentile arithmetic of the benchmark.

The yardstick's own: no PR that claims a gain may change how a median or a
tail is taken. Percentiles interpolate linearly between order statistics
(the definition numpy's default uses), so a p95 of n samples reads between
samples 0.95*(n-1) and the next.
"""

from __future__ import annotations

import statistics
import time

# process start, as close as Python lets us see it: run.py imports this
# module before anything heavy, so `setup_s` spans interpreter start-up of
# everything after it (jax import, data, weights, compilation, warm-up)
T_PROCESS_START = time.perf_counter()

CANDIDATE_TAILS = (50.0, 90.0, 95.0, 99.0, 99.9)


def now() -> float:
    """The one clock every host-side stamp of the benchmark reads."""
    return time.perf_counter()


def median(values) -> float:
    vals = sorted(values)
    if not vals:
        raise ValueError("median of no samples")
    return float(statistics.median(vals))


def percentile(values, q: float) -> float:
    """q in [0, 100]; linear interpolation between order statistics."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    if len(vals) == 1:
        return float(vals[0])
    rank = (q / 100.0) * (len(vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (rank - lo))


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(round(n * (100.0 - q) / 100.0, 9))


def highest_supported_tail(n: int, min_beyond: int = 10) -> float | None:
    """The highest of the candidate percentiles that has at least
    `min_beyond` samples beyond it (a p95 over a dozen requests is a
    maximum, not a tail). None when not even the median has."""
    best = None
    for q in CANDIDATE_TAILS:
        if samples_beyond(n, q) >= min_beyond:
            best = q
    return best


def summarize(values) -> dict:
    """Median, the highest supported tail and the sample count: what the
    information lines print beside every timing."""
    n = len(values)
    if not n:
        return {"n": 0}
    q = highest_supported_tail(n)
    out = {"n": n, "median": median(values)}
    if q is not None:
        out["tail_q"] = q
        out["tail"] = percentile(values, q)
    return out
