"""End-to-end metrics from what the clients saw (host clock).

Tails are taken over every request due inside the window, times from
when the request was due; tokens/s over every token that arrived inside
the window, whichever request it belongs to.
"""
from __future__ import annotations

import math


def pct(xs, q: float) -> float:
    """Linear-interpolation quantile (numpy's default), ``q`` in [0, 1]."""
    s = sorted(xs)
    if not s:
        raise ValueError("no samples")
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def window_records(run):
    return [r for r in run.records if r.arrival.phase == "window"]


def ttfts_ms(run):
    return [(r.times[0] - r.due) * 1e3 for r in window_records(run) if r.times]


def gaps_ms(run):
    out = []
    for r in window_records(run):
        out += [(b - a) * 1e3 for a, b in zip(r.times, r.times[1:])]
    return out


def tokens_in_window(run) -> int:
    return sum(1 for r in run.records for t in r.times
               if run.open <= t < run.close)


METRICS = {
    "ttft_p90_ms": lambda run: pct(ttfts_ms(run), 0.90),
    "itl_p50_ms": lambda run: pct(gaps_ms(run), 0.50),
    "itl_p95_ms": lambda run: pct(gaps_ms(run), 0.95),
    "tokens_per_s": lambda run: tokens_in_window(run) / (run.close - run.open),
}


def failures(run):
    """(attempted, failed): window requests, and those not served whole."""
    win = window_records(run)
    return len(win), sum(1 for r in win if not r.complete)
