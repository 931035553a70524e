"""Cross-request compiled-executable cache.

A serving fleet pays XLA lowering once per *distinct executable*, not
once per request: the executable is fully determined by the model
architecture, the shape bucket it serves, the committed
:class:`~repro.core.schedule.ScheduleBundle` baked in as the jit static
argument, and the backend.  :class:`ExecutableCache` keys compiled
prefill/decode step functions by exactly that tuple, so a dispatcher
commit (a new bundle) triggers at most one re-AOT session-wide instead
of once per ``generate`` call, and repeat traffic on a warm bucket
compiles nothing at all.

Eviction is LRU by executable count — compiled executables pin device
code, so a long-lived session serving many buckets must bound them.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ExecKey:
    """Identity of one compiled step function.

    ``length`` is the role's shape projection: the padded prompt length
    for prefill, the padded total (KV-capacity) length for decode —
    keying each role by only the dimension its executable depends on
    maximises sharing (requests with different decode budgets share one
    prefill executable, and vice versa).

    ``detail`` disambiguates executables whose traced shapes differ for
    reasons the other fields cannot express — the paged decode step keys
    on ``("paged", block_size, max_blocks)`` so it never collides with a
    monolithic-cache decode step of the same (batch, length).  It must
    be hashable; None (the default) keeps legacy keys unchanged.
    """

    arch: str
    role: str  # "prefill" | "decode"
    batch: int
    length: int
    schedules: Optional[Any]  # frozen ScheduleBundle (hashable) or None
    backend: str
    detail: Optional[Any] = None


class ExecutableCache:
    """LRU cache of compiled executables keyed by :class:`ExecKey`.

    ``get(key, builder)`` returns ``(executable, hit)``; on a miss the
    builder runs (one AOT compile), the result is inserted, and the
    least-recently-used entry is evicted if over capacity.  Counters
    (`hits`, `misses`, `evictions`, `compiles`) and the `compiled_log`
    of keys built feed :class:`~repro.serving.session.SessionStats` and
    the compile-amortisation assertions in the tests.
    """

    def __init__(self, capacity: int = 16):
        """Create an empty cache bounded to ``capacity`` executables."""
        if capacity < 1:
            raise ValueError("ExecutableCache capacity must be >= 1")
        self.capacity = capacity
        self._entries: "OrderedDict[ExecKey, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        self.compiled_log: List[ExecKey] = []

    def __len__(self) -> int:
        """Number of cached executables."""
        return len(self._entries)

    def __contains__(self, key: ExecKey) -> bool:
        """Alias for :meth:`contains` (no LRU/counter side effects)."""
        return key in self._entries

    def contains(self, key: ExecKey) -> bool:
        """Probe without touching LRU order or counters (used to decide
        whether a bundle switch is free before spending compile budget)."""
        return key in self._entries

    def get(self, key: ExecKey, builder: Callable[[], Any],
            ) -> Tuple[Any, bool]:
        """Return ``(executable, hit)``, building + inserting on a miss."""
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return exe, True
            self.misses += 1
        # Build outside the lock: XLA lowering can take seconds and the
        # cache must stay probeable meanwhile.
        exe = builder()
        with self._lock:
            if key not in self._entries:
                self.compiles += 1
                self.compiled_log.append(key)
                self._entries[key] = exe
                while len(self._entries) > self.capacity:
                    self._entries.popitem(last=False)
                    self.evictions += 1
            self._entries.move_to_end(key)
            return self._entries[key], False

    def items(self) -> List[Tuple[ExecKey, Any]]:
        """Snapshot of the cached ``(key, executable)`` pairs, without
        LRU or counter side effects (what a check of the compiled code
        walks)."""
        with self._lock:
            return list(self._entries.items())

    def compiled_roles(self) -> Dict[str, int]:
        """Compile counts per role (``{"prefill": n, "decode": m}``)."""
        out: Dict[str, int] = {}
        for k in self.compiled_log:
            out[k.role] = out.get(k.role, 0) + 1
        return out

    def stats(self) -> Dict[str, int]:
        """Counter snapshot (entries/capacity/hits/misses/evictions/compiles)."""
        return {
            "entries": len(self._entries),
            "capacity": self.capacity,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "compiles": self.compiles,
        }

    @property
    def hit_rate(self) -> float:
        """hits / (hits + misses), 0.0 before any lookup."""
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


__all__ = ["ExecKey", "ExecutableCache"]
