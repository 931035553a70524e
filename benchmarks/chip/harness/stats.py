"""Order statistics over every sample, and the spread of repeated runs.

Tails are taken over all requests of the window.  A request that has not
reached the event by the end of the window is counted at its age then
(``censored``), so a stalled engine reads as slow and never as missing.
"""
from __future__ import annotations

import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) with linear interpolation between
    closest ranks; None for no samples."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def censored(starts: Iterable[float], events: Iterable[Optional[float]],
             end: float) -> List[float]:
    """Per-request latency from ``start`` to ``event``; an event that has
    not happened by ``end`` (None or later) counts as ``end - start``."""
    out = []
    for s, e in zip(starts, events):
        out.append((e if e is not None and e <= end else end) - s)
    return out


def gaps(times: Sequence[float], end: float) -> List[float]:
    """Gaps between consecutive timestamps, up to and including the last
    timestamp at or before ``end``."""
    ts = [t for t in times if t <= end]
    return [b - a for a, b in zip(ts, ts[1:])]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
