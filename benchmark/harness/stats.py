"""Statistics over request records. Pure functions: no clock, no I/O."""

from __future__ import annotations

import statistics


def percentile(values, pct: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * pct / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the spread
    the bounds in BENCHMARK.json were set from."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def ttft_ms(rec, censor_at: float) -> float:
    """Milliseconds from when the request was DUE (not sent) to its first
    token. A request that failed, was refused or delivered nothing by the
    drain limit is as late as the run could see: ``censor_at - due``."""
    if rec.ok and rec.events:
        return 1000.0 * (rec.events[0][0] - rec.due)
    return 1000.0 * (censor_at - rec.due)


def tpot_ms(rec):
    """Per request: (last token - first token) / (tokens - 1). None where
    the request delivered fewer than two token groups to divide over."""
    if not rec.ok or len(rec.events) < 2:
        return None
    tokens = sum(n for _, n in rec.events)
    # the first group's tokens arrive together with the first token
    later = tokens - rec.events[0][1]
    if later < 1:
        return None
    return 1000.0 * (rec.events[-1][0] - rec.events[0][0]) / later


def tpots_with_worst(records) -> list:
    """Every request's tpot; a failed request takes the worst one seen."""
    got = [(r, tpot_ms(r)) for r in records]
    seen = [v for _, v in got if v is not None]
    if not seen:
        return []
    worst = max(seen)
    return [v if v is not None else worst
            for r, v in got if v is not None or not r.ok]


def tokens_between(records, t0: float, t1: float) -> int:
    """Output tokens delivered to clients at t0 <= t < t1."""
    return sum(n for r in records for t, n in r.events if t0 <= t < t1)


def pooled_gaps_ms(records) -> list:
    """Gaps between consecutive streamed token groups, all requests."""
    out = []
    for r in records:
        ts = [t for t, _ in r.events]
        out.extend(1000.0 * (b - a) for a, b in zip(ts, ts[1:]))
    return out


def met_both_limits(records, limits: dict, censor_at: float) -> int:
    """How many of ``records`` finished with their first token within
    ``limits.ttft_ms`` of being due and their time per output token within
    ``limits.tpot_ms``; a failed request misses."""
    return sum(1 for r in records
               if r.ok and ttft_ms(r, censor_at) <= limits["ttft_ms"]
               and (tpot_ms(r) or 0.0) <= limits["tpot_ms"])
