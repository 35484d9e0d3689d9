"""The arithmetic of the end-to-end metrics, kept apart so that tests can
hold it to known inputs."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not samples:
        raise ValueError("no samples")
    s = sorted(samples)
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def group_rates(tokens_per_group: int, group_seconds: Sequence[float],
                window_s: Optional[float] = None) -> Dict[str, float]:
    """`window_tok_s`: all tokens over all of the window (`window_s`, or the
    sum of the groups where that is not given), which carries every stall;
    `median_tok_s`: tokens of one group over the MEDIAN group time, which a
    group in which the host or the chip stalled does not move."""
    if not group_seconds:
        raise ValueError("no timed group")
    if window_s is None:
        window_s = sum(group_seconds)
    return {
        "window_tok_s": tokens_per_group * len(group_seconds) / window_s,
        "median_tok_s": tokens_per_group / statistics.median(group_seconds),
    }


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance over the median, as the contract measures a
    set of runs (`statistics.quantiles(values, n=4)`)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values: Sequence[float]) -> float:
    """The spread that decides whether a new cell is admitted: the set's
    inter-quartile distance, or that of the set without the run farthest
    from the median where that is narrower, over the set's median. The
    driver's refusal at PR 35 (ledger) gives the rule: "A spread leaves out
    the run farthest from its median where that narrows it. For a workload
    that is new, or measured anew, the mean of the two spreads may be at
    most 50% of the bound", the bound being the metric's share of the
    median of the runs (`measure.admission`). These are the words of that
    refusal, as far as they define the rule, not the driver's code: which
    of two equally far runs goes is this function's own choice. On the one
    cell that both have read (Solar r64: 0.176 ms here at PR 36, 0.197 ms
    in the check's note at PR 34, half the bound 0.169) the two are of one
    size, 11% apart, which is what two draws of six seeds differ by."""
    if len(values) < 3:
        return spread(values)
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1]
    quartiles = [statistics.quantiles(v, n=4) for v in (values, kept)]
    return min(q3 - q1 for q1, _, q3 in quartiles) / mid


def union_seconds(intervals: Sequence[Sequence[float]]) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Sequence[float]], lo: float, hi: float
         ) -> List[List[float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append([at, min(s, hi)])
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append([at, hi])
    return [g for g in out if g[1] > g[0]]
