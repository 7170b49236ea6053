"""The benchmark's own arithmetic: percentiles, window accounting and
the spread rule of the contract. Plain Python, tested on the CPU."""

from __future__ import annotations

import statistics


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def in_window(calls: list, t0: float, t1: float) -> list:
    """Calls that COMPLETE inside [t0, t1), whenever they started: in
    steady state that is unbiased, while "started and completed inside"
    keeps the short calls."""
    return [c for c in calls if t0 <= c.done < t1]


def window_metrics(calls: list, t0: float, t1: float) -> dict:
    """End-to-end numbers of one window from the call log."""
    inside = in_window(calls, t0, t1)
    good = [c for c in inside if c.ok]
    out = {
        "attempted": len(inside),
        "failed": len(inside) - len(good),
        "out_tok_s": sum(c.completion_tokens for c in good) / (t1 - t0),
    }
    if good:
        ms = [c.ms for c in good]
        out["call_ms_p50"] = percentile(ms, 50)
        out["call_ms_p95"] = percentile(ms, 95)
        out["call_ms_mean"] = sum(ms) / len(ms)
    return out


def spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's `statistics.quantiles(values, n=4)`."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def delta(after: dict, before: dict, key: str) -> float:
    return float(after.get(key, 0)) - float(before.get(key, 0))


def ratio_of_deltas(after: dict, before: dict, num: str, den: str):
    """Δnum / Δden over a window, or None when nothing was counted."""
    d = delta(after, before, den)
    return delta(after, before, num) / d if d > 0 else None
