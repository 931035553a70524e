"""Device idle time under the engine's own spans.

``Trace.spans`` holds the engine's complete spans as (name, start, end)
on the trace clock.  The engine splits each decode step and each
admission into host phases (``serve.decode_step.upload`` ... ``check``,
``serve.admit.prepare`` ... ``place``) and marks the time the queue's
head waits with a row free (``serve.queue.held``).  A program that
records none of these yields no reading: the readers return None.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from harness.trace import Interval, clip, total, union

# The idle gaps counted, as in ``Trace.idle_gaps``: shorter ones are
# bubbles between the operations of one program.
MIN_GAP_NS = 20_000

DECODE_PHASES = ("serve.decode_step.upload", "serve.decode_step.launch",
                 "serve.decode_step.fetch", "serve.decode_step.check")
ADMIT_PHASES = ("serve.admit.prepare", "serve.prefill",
                "serve.admit.first_token", "serve.admit.place")
# Recorded once per decode step, by the engines that record phases.
PHASED = "serve.decode_step.launch"


def intervals(tr, names: Iterable[str]) -> List[Interval]:
    """The union of the spans with these names, clipped to the window."""
    names = set(names)
    lo, hi = tr.window
    return union(clip([(s, e) for n, s, e in tr.spans if n in names],
                      lo, hi))


def starts_in_window(tr, name: str) -> int:
    """How many spans called ``name`` start inside the window."""
    lo, hi = tr.window
    return sum(1 for n, s, _ in tr.spans if n == name and lo <= s < hi)


def has_phases(tr) -> bool:
    """Whether the program recorded its host phases at all."""
    return any(n == PHASED for n, _, _ in tr.spans)


def idle_gaps(tr, device: str) -> List[Interval]:
    """The device's idle gaps in the window of at least MIN_GAP_NS."""
    lo, hi = tr.window
    edges = [lo] + [x for iv in tr.busy_intervals(device) for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2])
            if b - a >= MIN_GAP_NS]


def idle_inside_s(tr, spans: List[Interval]) -> float:
    """Seconds of device idle (gaps of at least MIN_GAP_NS) inside the
    disjoint ``spans``, averaged over the devices."""
    ns = 0
    for dev in tr.ops:
        for a, b in idle_gaps(tr, dev):
            ns += total(clip(spans, a, b))
    return ns / max(len(tr.ops), 1) / 1e9


def idle_ms_per(tr, phases, per: str) -> Optional[float]:
    """Device idle inside the union of ``phases``, in ms per span
    ``per`` that starts in the window; None without a device, without
    phases or without such a span."""
    if not tr.ops or not has_phases(tr):
        return None
    n = starts_in_window(tr, per)
    if not n:
        return None
    return 1e3 * idle_inside_s(tr, intervals(tr, phases)) / n
