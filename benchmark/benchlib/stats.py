"""The arithmetic of the end-to-end metrics: percentiles and rates over the
requests of one window, and the spread of one metric over a set of runs."""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass


@dataclass
class Request:
    """One request of the window, on the harness's clock (seconds from the
    window's start)."""
    query: int          # index into the pool
    sent: float
    done: float
    status: int         # HTTP status; 0 = no answer (error or timeout)
    took_ms: float | None = None
    ids: list | None = None      # hits' _id as int, in rank order
    scores: list | None = None   # hits' _score
    total: object = None         # hits.total as answered

    @property
    def ok(self) -> bool:
        return self.status == 200

    @property
    def latency_ms(self) -> float:
        return (self.done - self.sent) * 1e3


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (NumPy's default), over every value given."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window_summary(requests: list[Request], seconds: float) -> dict:
    """Latency over every request sent in the window and answered 200, the
    wait of a stall included; rate over those answered before the window
    closed, divided by the whole window."""
    ok = [r for r in requests if r.ok]
    lat = [r.latency_ms for r in ok]
    out = {"attempted": len(requests), "failed": len(requests) - len(ok),
           "answered_in_window": sum(1 for r in ok if r.done <= seconds)}
    if lat:
        out["p50_ms"] = percentile(lat, 50)
        out["p95_ms"] = percentile(lat, 95)
        out["mean_ms"] = sum(lat) / len(lat)
    out["qps"] = out["answered_in_window"] / seconds
    return out


def spread(values, trimmed: bool = False) -> float:
    """How far the runs of one set lie apart in one metric: the distance
    between the first and the third quartile, as
    `statistics.quantiles(values, n=4)` gives them, as a share of the median.
    `trimmed` leaves out the run farthest from the median where that narrows
    the spread: the check's measure of whether a bound is too tight (the mean
    of two sets' trimmed spreads may be at most half of it); untrimmed, over
    all runs, its measure of whether one is too loose (README.md, Bounds)."""
    v = [float(x) for x in values]
    if len(v) < 2:
        raise ValueError("a spread needs two runs or more")
    q1, _, q3 = statistics.quantiles(v, n=4)
    med = statistics.median(v)
    out = (q3 - q1) / med
    if trimmed and len(v) > 3:  # three runs or more stay
        far = max(range(len(v)), key=lambda i: abs(v[i] - med))
        out = min(out, spread(v[:far] + v[far + 1:]))
    return out
