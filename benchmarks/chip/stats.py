"""Rate and percentile arithmetic over what the window served.

Times are host-clock seconds: each output token is timed at the return of
the tick that produced it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Served:
    """One request as the harness saw it."""

    index: int
    token_times: List[float] = field(default_factory=list)
    failed: bool = False
    finished: bool = False


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default); None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def tokens_in(reqs: Sequence[Served], lo: float, hi: float) -> int:
    return sum(1 for r in reqs for t in r.token_times if lo < t <= hi)


def rate(reqs: Sequence[Served], lo: float, hi: float) -> float:
    """Output tokens completed in (lo, hi] per second of it."""
    return tokens_in(reqs, lo, hi) / (hi - lo)


def inter_token_gaps(reqs: Sequence[Served], lo: float, hi: float) -> List[float]:
    """Gaps between consecutive tokens of each request, for every token
    that arrived in (lo, hi]."""
    out = []
    for r in reqs:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]) if lo < b <= hi)
    return out


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, by
    ``statistics.quantiles(values, n=4)`` (the spread the bounds rest on)."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
