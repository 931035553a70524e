"""The device trace of a ``--trace 1`` run, reduced to intervals.

The run records JAX's profiler over the measured window (host Python
tracing off) and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``;
on each, the line ``XLA Ops`` holds one event per operation the chip
ran (fusions, custom calls such as Pallas kernels) and ``XLA Modules``
one event per executable run.  The benchmark's own annotations
(``bench.window``, ``bench.drain``, ``bench.wait_arrival``,
``bench.submit``) are host events on the same clock; ``bench.window``
fixes the window.  The engine's spans (``serve.prefill``,
``serve.decode_step``, ...) come from its own tracer on the host's
``perf_counter`` and are put on the trace clock through the window's
start.

Everything a metric reader needs is here: the union of busy intervals,
time and counts per executable or per operation name, and the idle gaps
labelled by what the host was doing.
"""
from __future__ import annotations

import bisect
import collections
import glob
import pathlib
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from harness import kernels

Interval = Tuple[int, int]


def start(log_dir: pathlib.Path) -> None:
    """Start JAX's profiler into ``log_dir``, host Python tracing off."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted union of half-open intervals."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


class Trace:
    """Device and host events of one traced window."""

    def __init__(self, ops: Dict[str, List[Tuple[str, int, int]]],
                 modules: Dict[str, List[Tuple[str, int, int]]],
                 host: List[Tuple[str, int, int]],
                 window: Interval,
                 spans: Sequence[Tuple[str, int, int]] = ()):
        self.ops = ops            # device -> [(op name, start, end)]
        self.modules = modules    # device -> [(module name, start, end)]
        self.host = host          # [(annotation, start, end)]
        self.window = window      # trace-clock ns
        self.spans = list(spans)  # engine spans on the trace clock

    # ------------------------------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, device: str) -> List[Interval]:
        lo, hi = self.window
        return union(clip([(s, e) for _, s, e in self.ops[device]], lo, hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy_intervals(d)) for d in self.ops) \
            / len(self.ops) / 1e9

    def _events(self, table, match: Callable[[str], bool]):
        lo, hi = self.window
        out = []
        for d, evs in table.items():
            for name, s, e in evs:
                if match(name) and min(e, hi) > max(s, lo):
                    out.append((d, name, max(s, lo), min(e, hi)))
        return out

    def op_events(self, match: Callable[[str], bool]):
        """(device, name, start, end) of the window's operations whose
        name matches, clipped to the window."""
        return self._events(self.ops, match)

    def module_events(self, match: Callable[[str], bool]):
        """(device, name, start, end) of the window's executable runs
        whose name matches, clipped to the window."""
        return self._events(self.modules, match)

    # ------------------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[List]:
        """The operations that took most device time, as
        ``<executable>/<operation>`` (containers such as a layer loop's
        ``while`` left out: their children are counted)."""
        lo, hi = self.window
        agg: Dict[str, int] = collections.Counter()
        for dev, evs in self.ops.items():
            mods = sorted((s, e, kernels.module_fn(n))
                          for n, s, e in self.modules.get(dev, []))
            starts = [m[0] for m in mods]
            for name, s, e in evs:
                s, e = max(s, lo), min(e, hi)
                op = kernels.op_name(name)
                if e <= s or op in kernels.CONTAINERS:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "-"
                agg[f"{mod}/{op}"] += e - s
        n = max(len(self.ops), 1)
        return [[name, ns / n / 1e9] for name, ns in agg.most_common(k)]

    def idle_gaps(self, k: int = 10, min_ns: int = 20_000) -> List[List]:
        """Idle device time in the window, summed by what the host was
        doing in the middle of each gap: the innermost engine span, else
        the benchmark's innermost annotation.  Gaps under ``min_ns``
        (bubbles between the operations of one program) are summed as
        ``device:between-ops``."""
        lo, hi = self.window
        spans = sorted(self.spans, key=lambda x: x[1])
        annots = sorted(((n, s, e) for n, s, e in self.host
                         if n.startswith("bench.") and n != "bench.window"),
                        key=lambda x: x[1])

        def innermost(table, starts, mid):
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(i - 64, -1), -1):
                if table[j][2] > mid:
                    return table[j][0]
            return None

        span_starts = [x[1] for x in spans]
        annot_starts = [x[1] for x in annots]
        agg: Dict[str, int] = collections.Counter()
        for dev in self.ops:
            busy = self.busy_intervals(dev)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                if b - a < min_ns:
                    agg["device:between-ops"] += b - a
                    continue
                mid = (a + b) // 2
                label = (innermost(spans, span_starts, mid)
                         or innermost(annots, annot_starts, mid)
                         or "host:other")
                agg[label] += b - a
        n = max(len(self.ops), 1)
        return [[name, ns / n / 1e9] for name, ns in agg.most_common(k)]

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _device_name(plane_name: str) -> Optional[str]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return m.group(0) if m else None


def from_profile(pd, spans=(), perf_t0: Optional[float] = None,
                 window_name: str = "bench.window") -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``.  ``spans``
    are the engine's (name, start, end) in ``perf_counter`` seconds;
    ``perf_t0``, the window's start on that clock, puts them on the
    trace clock."""
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    modules: Dict[str, List[Tuple[str, int, int]]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        dev = _device_name(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA Ops":
                ops.setdefault(dev, []).extend(
                    (e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif dev is not None and line.name == "XLA Modules":
                modules.setdefault(dev, []).extend(
                    (e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    wins = [(s, e) for n, s, e in host if n == window_name]
    if not wins:
        raise ValueError(f"the trace holds no {window_name!r} annotation")
    window = wins[0]
    on_trace = []
    if perf_t0 is not None:
        on_trace = [(n, window[0] + int((s - perf_t0) * 1e9),
                     window[0] + int((e - perf_t0) * 1e9))
                    for n, s, e in spans]
    return Trace(ops, modules, host, window, on_trace)


def load(trace_dir: pathlib.Path, spans=(),
         perf_t0: Optional[float] = None) -> Trace:
    """The trace the run wrote under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]), spans,
                        perf_t0)
