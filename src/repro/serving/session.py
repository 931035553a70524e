"""ServeSession — a persistent serving engine across requests.

PR 3/4 built the adaptive loop (tune → select → observe → commit →
recompile) for a *single* ``generate`` call; a production fleet serves a
stream of heterogeneous requests, so the expensive artefacts must be
amortised *across* them.  The session owns:

* an **admission queue** of :class:`Request`\\ s with per-request
  prompt / new-token budgets,
* **shape bucketing + continuous batching**: pending requests are
  grouped by padded prompt bucket and the (batch, padded-length) bucket
  whose *measured* tok/s from the
  :class:`~repro.runtime.dispatch.DispatchService` per-shape
  observations is best is chosen (cold shapes fall back to the
  cost model's prediction),
* an **in-flight engine** (dense/MoE/SSM, greedy): decoding runs as a
  step loop over a fixed set of rows backed by a **block-paged KV
  cache** (:mod:`repro.serving.paged_kv`); at every step boundary
  finished sequences retire and free their blocks, and queued requests
  are admitted — batch-1 masked prefill, prompt KV scattered into pool
  blocks — while the free-block budget allows, so a short request never
  waits out a long batchmate's full decode,
* a **cross-request executable cache**
  (:class:`~repro.serving.cache.ExecutableCache`) keyed by
  ``(arch, bucket, ScheduleBundle, backend)``, so a dispatcher commit
  triggers at most one re-AOT session-wide instead of once per
  ``generate`` call — and a commit whose executable is already cached
  switches for free, without spending compile budget,
* :class:`SessionStats`: per-bucket tok/s, cache hits/misses/evictions,
  re-AOTs, queue-latency percentiles, and the fault-tolerance ledger
  (terminal-state counters, degradation flag, recorded events),
* **fault tolerance**: every request ends in a terminal
  :class:`RequestState` with a reason — never-fits requests are
  REJECTED per-request instead of raising out of :meth:`drain`,
  ``deadline_s`` / ``max_queue_s`` budgets time out or shed requests,
  non-finite logits retire only the poisoned row (blocks freed, stream
  unaffected), AOT-compile failures retry with capped backoff and then
  degrade per-bucket to the reference backend
  (``fallback_backend="reference"``), and a
  :class:`~repro.runtime.ft.StragglerMonitor` watches decode-step times
  (``on_straggler`` can shrink admission).  ``docs/SERVING.md`` §Failure
  semantics is the operator contract; :mod:`repro.serving.faults`
  injects each of these deterministically for tests and the chaos
  bench.

``runtime/serve_loop.generate`` is a thin single-request client of this
class (an ephemeral session per call reproduces the PR-4 behaviour
exactly); long-lived servers construct one session and ``submit`` /
``drain`` against it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry as reg
from repro.models.model_zoo import (Model, bucket_length,
                                    left_pad_prompts, prompt_starts)
from repro.obs.events import Event
from repro.obs.recorder import POSTMORTEM_KINDS
from repro.obs.telemetry import NULL_TELEMETRY
from repro.obs.trace import QUEUE_TID
from repro.runtime.ft import StragglerMonitor
from repro.serving.bucketing import (Bucket, candidate_buckets,
                                     pick_bucket)
from repro.serving.cache import ExecKey, ExecutableCache
from repro.serving.paged_kv import BlockAllocator, blocks_needed

log = logging.getLogger("repro.serving")

_REQUEST_IDS = itertools.count()

# Shared no-op context manager: the telemetry-off span fast path costs
# one attribute check and this singleton, never a tracer call.
_NULL_SPAN = contextlib.nullcontext()

# Bucket of results that never reached an engine row (rejected, shed,
# cancelled while queued): there is no meaningful geometry to report.
_NULL_BUCKET = Bucket(0, 0, 0)


class RequestState:
    """Request lifecycle states; all but QUEUED/RUNNING are terminal.

    * ``COMPLETED`` — full decode budget delivered.
    * ``REJECTED`` — can never be served by this session's configuration
      (e.g. the whole ``prompt + budget`` KV footprint exceeds the pool).
    * ``TIMED_OUT`` — ``deadline_s`` blown (queued or mid-decode, with
      partial tokens) or shed by ``max_queue_s`` while queued.
    * ``CANCELLED`` — :meth:`ServeSession.cancel` (partial tokens when
      the request was already decoding).
    * ``FAILED`` — a step-level fault (non-finite logits, kernel
      exception) retired the row; partial tokens, reason recorded.
    """

    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    COMPLETED = "COMPLETED"
    REJECTED = "REJECTED"
    TIMED_OUT = "TIMED_OUT"
    CANCELLED = "CANCELLED"
    FAILED = "FAILED"


TERMINAL_STATES = frozenset({
    RequestState.COMPLETED, RequestState.REJECTED, RequestState.TIMED_OUT,
    RequestState.CANCELLED, RequestState.FAILED})


@dataclasses.dataclass
class Request:
    """One admitted generation request (a single sequence)."""

    tokens: np.ndarray              # [S] int32 prompt
    max_new_tokens: int
    request_id: str
    submitted_at: float             # session clock at submission
    extras: Optional[Dict[str, np.ndarray]] = None  # per-row modality data
    deadline_s: Optional[float] = None  # submit -> last token budget


@dataclasses.dataclass
class RequestResult:
    """Per-request outcome returned by :meth:`ServeSession.drain`.

    ``state`` is a terminal :class:`RequestState`; for anything but
    ``COMPLETED`` the ``tokens`` may be partial (timed out / cancelled /
    failed mid-decode) or empty (never admitted) and ``reason`` says
    why.
    """

    request_id: str
    tokens: np.ndarray              # [<= max_new_tokens] int32
    bucket: Bucket
    queue_s: float                  # admission -> batch start
    stats: Any                      # the group's ServeStats (shared)
    state: str = RequestState.COMPLETED
    reason: Optional[str] = None


@dataclasses.dataclass
class SessionStats:
    """What the session did, fleet-wide."""

    requests: int = 0
    batches: int = 0
    tokens_generated: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    recompiles: int = 0             # mid-stream re-AOTs (compile spent)
    free_switches: int = 0          # bundle switches served from cache
    commits_seen: int = 0
    steps: int = 0                  # in-flight engine decode steps
    inflight_admissions: int = 0    # requests admitted at step boundaries
    compactions: int = 0            # paged-pool defragmentation passes
    # --- fault-tolerance ledger (ISSUE 7) ---
    fallbacks: int = 0              # AOT lowerings that fell back to jit
    compile_retries: int = 0        # failed AOT attempts that were retried
    degraded: bool = False          # any bucket fell back to reference
    degraded_buckets: int = 0       # buckets running the reference backend
    rejected: int = 0               # never-fits requests (REJECTED)
    timed_out: int = 0              # deadline/queue-budget expiries
    shed: int = 0                   # subset of timed_out: max_queue_s shed
    cancelled: int = 0              # client cancellations
    failed: int = 0                 # step-level faults (poison rows, ...)
    poisoned_rows: int = 0          # rows retired on non-finite logits
    stragglers: int = 0             # slow-step events from the monitor
    # Structured operational events (one schema stack-wide; see
    # repro.obs.events.Event) — faults, degradations, stragglers.
    events: List[Event] = dataclasses.field(default_factory=list)
    queue_s: List[float] = dataclasses.field(default_factory=list)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    per_bucket: Dict[Bucket, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    cache: Dict[str, int] = dataclasses.field(default_factory=dict)

    def queue_percentiles(self) -> Tuple[float, float]:
        """(p50, p95) queue latency in seconds (0.0 with no samples)."""
        if not self.queue_s:
            return 0.0, 0.0
        a = np.asarray(self.queue_s, dtype=np.float64)
        return float(np.percentile(a, 50)), float(np.percentile(a, 95))

    def ttft_percentiles(self) -> Tuple[float, float]:
        """(p50, p95) time-to-first-token in seconds (0.0 no samples)."""
        if not self.ttft_s:
            return 0.0, 0.0
        a = np.asarray(self.ttft_s, dtype=np.float64)
        return float(np.percentile(a, 50)), float(np.percentile(a, 95))

    def bucket_tok_s(self) -> Dict[Bucket, float]:
        """Goodput tokens/s per bucket (delivered tokens / decode wall)."""
        return {b: e["tokens"] / max(e["decode_s"], 1e-9)
                for b, e in self.per_bucket.items()}

    def to_dict(self) -> Dict[str, Any]:
        """JSON-ready summary (what ``launch/serve`` and benches print)."""
        p50, p95 = self.queue_percentiles()
        t50, t95 = self.ttft_percentiles()
        hits = self.cache.get("hits", 0)
        total = hits + self.cache.get("misses", 0)
        return {
            "requests": self.requests,
            "batches": self.batches,
            "tokens_generated": self.tokens_generated,
            "decode_tok_s": (self.tokens_generated
                             / max(self.decode_s, 1e-9)),
            "recompiles": self.recompiles,
            "free_switches": self.free_switches,
            "commits_seen": self.commits_seen,
            "steps": self.steps,
            "inflight_admissions": self.inflight_admissions,
            "compactions": self.compactions,
            "fallbacks": self.fallbacks,
            "compile_retries": self.compile_retries,
            "degraded": self.degraded,
            "degraded_buckets": self.degraded_buckets,
            "rejected": self.rejected,
            "timed_out": self.timed_out,
            "shed": self.shed,
            "cancelled": self.cancelled,
            "failed": self.failed,
            "poisoned_rows": self.poisoned_rows,
            "stragglers": self.stragglers,
            "events": [e.as_dict() for e in self.events],
            "queue_p50_s": p50,
            "queue_p95_s": p95,
            "ttft_p50_s": t50,
            "ttft_p95_s": t95,
            "cache": dict(self.cache),
            "cache_hit_rate": hits / total if total else 0.0,
            "buckets": {
                f"b{b.batch}xp{b.prompt_len}xt{b.total_len}": {
                    **{k: float(v) for k, v in e.items()},
                    "tok_s": e["tokens"] / max(e["decode_s"], 1e-9),
                }
                for b, e in sorted(self.per_bucket.items())
            },
        }


class ServeSession:
    """Persistent serving engine: queue → bucket → cached executables.

    Parameters mirror ``serve_loop.generate`` (``dispatch``, ``backend``,
    ``registry``, ``max_recompiles``) plus the session-level knobs:
    ``batch_sizes`` (allowed continuous-batching batch dims),
    ``bucket_lengths`` (explicit padded-length grid; default power-of-2),
    ``cache_capacity`` (LRU executable bound), ``pad_id``, and the paged
    KV geometry — ``kv_block_size`` (token slots per pool block) and
    ``kv_blocks`` (pool size; None sizes the pool so every engine row can
    reach its full per-row capacity, a smaller explicit value exercises
    admission backpressure).

    Fault-tolerance knobs (ISSUE 7; see docs/SERVING.md §Failure
    semantics): ``request_deadline_s`` (default per-request submit →
    last-token budget; per-request ``submit(deadline_s=)`` overrides),
    ``max_queue_s`` (load shedding: queued longer than this →
    TIMED_OUT), ``fallback_backend`` ("reference" degrades a bucket's
    executables to the reference backend after ``compile_retries``
    failed AOT attempts; "none" keeps the un-lowered pallas fn),
    ``compile_retries`` / ``compile_backoff_s`` (capped exponential
    backoff between AOT attempts), ``nan_check`` (per-step finite-logits
    screen feeding poison-row isolation), ``straggler_threshold`` +
    ``on_straggler`` (slow-step hook; returning an int N holds admission
    for N step boundaries), and ``faults`` (a
    :class:`~repro.serving.faults.FaultInjector`, dev/test only).

    Reactive observability (ISSUE 10): ``watchdog`` (a
    :class:`~repro.obs.watchdog.PerformanceWatchdog`) is fed the decode
    slot's measured step times — fault-injected slowdowns included — at
    every step boundary plus the SLO samples (TTFT, queue wait,
    terminal outcomes, tok/s), and its drift/SLO events land in the
    session event ledger; ``recorder`` (a
    :class:`~repro.obs.recorder.FlightRecorder`) taps the same ledger
    and step spans, and any event whose kind is in
    ``repro.obs.recorder.POSTMORTEM_KINDS`` triggers a
    ``postmortem-<reason>.json`` dump.  Both default to the matching
    slot on the telemetry bundle, then to ``None``; with neither bound
    the engine executes the exact same instruction stream as before.
    """

    def __init__(self, model: Model, params, *,
                 dispatch=None,
                 backend: str = "reference",
                 registry: Optional[reg.TuningRegistry] = None,
                 max_recompiles: int = 1,
                 cache_capacity: int = 16,
                 batch_sizes: Sequence[int] = (1, 2, 4, 8),
                 bucket_lengths: Optional[Sequence[int]] = None,
                 temperature: float = 0.0,
                 pad_id: int = 0,
                 kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 request_deadline_s: Optional[float] = None,
                 max_queue_s: Optional[float] = None,
                 fallback_backend: str = "reference",
                 compile_retries: int = 2,
                 compile_backoff_s: float = 0.01,
                 nan_check: bool = True,
                 straggler_threshold: float = 3.0,
                 on_straggler=None,
                 faults=None,
                 telemetry=None,
                 watchdog=None,
                 recorder=None):
        """Validate the knobs and set up an empty queue + caches."""
        self.model = model
        self.params = params
        self.dispatch = dispatch
        self.backend = backend
        self.registry = registry
        self.max_recompiles = max_recompiles
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        if not self.batch_sizes or self.batch_sizes[0] < 1:
            raise ValueError(
                f"batch_sizes must be positive ints, got {batch_sizes!r}")
        self.bucket_lengths = (tuple(sorted(set(bucket_lengths)))
                               if bucket_lengths else None)
        self.temperature = temperature
        self.pad_id = pad_id
        if kv_block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        if kv_blocks is not None and kv_blocks < 2:
            raise ValueError(
                "kv_blocks must be >= 2 (block 0 is the reserved sink)")
        self.kv_block_size = int(kv_block_size)
        self.kv_blocks = None if kv_blocks is None else int(kv_blocks)
        if fallback_backend not in ("reference", "none"):
            raise ValueError(
                f"fallback_backend must be 'reference' or 'none', got "
                f"{fallback_backend!r}")
        if compile_retries < 0:
            raise ValueError("compile_retries must be >= 0")
        self.request_deadline_s = request_deadline_s
        self.max_queue_s = max_queue_s
        self.fallback_backend = fallback_backend
        self.compile_retries = int(compile_retries)
        self.compile_backoff_s = float(compile_backoff_s)
        self.nan_check = bool(nan_check)
        self.on_straggler = on_straggler
        self.exec_cache = ExecutableCache(cache_capacity)
        self.stats = SessionStats()
        self._queue: List[Request] = []
        self._done: List[RequestResult] = []    # finished outside drain
        self._cancelled: set = set()            # ids flagged for cancel
        self._running: set = set()              # ids currently on a row
        self._admission_hold = 0                # boundaries to skip admit
        self._step_count = 0                    # session-global step index
        self._faults = faults
        # Telemetry (ISSUE 8): a repro.obs.Telemetry bundle — metrics +
        # span tracer + per-request lifecycle log.  Defaults to the
        # shared disabled instance; every instrumentation site guards on
        # telemetry.enabled, so the off path never touches the tracer.
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        # Deadline/shedding decisions read this clock (tests swap in a
        # fake one for deterministic mid-decode timeouts); step timings
        # always use the real perf counter.
        self._clock = time.perf_counter
        self._straggler = StragglerMonitor(
            threshold=straggler_threshold,
            on_straggler=self._straggler_event)
        # Reactive layer (ISSUE 10): explicit parameters win, then the
        # telemetry bundle's slots, then None (measurement only).  Every
        # tap below guards on `is not None`, so a session without a
        # watchdog/recorder runs the identical instruction stream.
        self._watchdog = (watchdog if watchdog is not None
                          else self.telemetry.watchdog)
        self._recorder = (recorder if recorder is not None
                          else self.telemetry.recorder)
        if self._watchdog is not None:
            self._watchdog.bind(
                dispatch=dispatch, clock=self._clock,
                on_event=self._record_event,
                metrics=(self.telemetry.metrics
                         if self.telemetry.enabled else None))
        if self._recorder is not None:
            self._recorder.bind(clock=self._clock)
        if self.telemetry.enabled:
            self._register_instruments()

    # ------------------------------------------------------ telemetry
    def _register_instruments(self) -> None:
        """Pre-create the session's metric families (zero-valued) so
        exporters always include them, even before traffic or faults."""
        m = self.telemetry.metrics
        m.counter("serve.requests_submitted_total",
                  help="requests submitted to the session")
        m.counter("serve.inflight_admissions_total",
                  help="requests admitted at engine step boundaries")
        m.counter("serve.events_total",
                  help="structured operational events (faults, "
                       "degradations, stragglers)")
        m.counter("serve.exec_cache_hits_total",
                  help="executable-cache hits")
        m.counter("serve.exec_cache_misses_total",
                  help="executable-cache misses")
        m.counter("serve.aot_fallbacks_total",
                  help="AOT lowerings that fell back to the jit fn")
        m.counter("serve.compile_retries_total",
                  help="failed AOT attempts that were retried")
        m.histogram("serve.ttft_seconds",
                    help="submit -> first token latency, seconds")
        m.histogram("serve.decode_step_seconds",
                    help="engine decode step wall time, seconds")
        m.gauge("serve.kv_blocks_live", help="paged-KV blocks in use")
        m.gauge("serve.kv_blocks_free", help="paged-KV blocks free")
        m.gauge("serve.kv_fragmentation",
                help="paged-KV pool fragmentation [0,1]")

    def _span(self, name: str, **args):
        """Tracer span when telemetry is on; a shared no-op context
        manager otherwise (the null fast path)."""
        tel = self.telemetry
        if tel.enabled:
            return tel.tracer.span(name, **args)
        return _NULL_SPAN

    def _begin(self, name: str, **args):
        """Open a tracer span that :meth:`_end` records, for a region
        that is not one ``with`` block; None, with no tracer call, when
        telemetry is off."""
        tel = self.telemetry
        return tel.tracer.begin(name, **args) if tel.enabled else None

    def _end(self, opened, **args) -> None:
        """Record a span :meth:`_begin` opened (nothing for None)."""
        if opened is not None:
            self.telemetry.tracer.end(opened, **args)

    def _event(self, kind: str, step: Optional[int] = None,
               request_id: Optional[str] = None, **data: Any) -> None:
        """Record one structured :class:`~repro.obs.events.Event`."""
        self._record_event(Event(kind=kind, step=step,
                                 request_id=request_id,
                                 ts=self._clock(), data=data))

    def _record_event(self, ev: Event) -> None:
        """Append an event to the ledger and mirror it into telemetry
        (per-kind counters + a trace instant).  With a flight recorder
        bound the event also lands in its ring, and postmortem-worthy
        kinds (faults, SLO pages, drift alarms) trigger a bundle dump."""
        self.stats.events.append(ev)
        tel = self.telemetry
        if tel.enabled:
            tel.metrics.counter("serve.events_total").inc()
            tel.metrics.counter(f"serve.events.{ev.kind}_total").inc()
            tel.tracer.instant(f"event:{ev.kind}", step=ev.step,
                               request_id=ev.request_id)
        rec = self._recorder
        if rec is not None:
            rec.record_event(ev)
            if ev.kind in POSTMORTEM_KINDS:
                self.dump_postmortem(ev.kind)

    def dump_postmortem(self, reason: str) -> Optional[str]:
        """Write ``postmortem-<reason>.json`` via the bound flight
        recorder (None without one): the recorder's recent timeline and
        allocator state, plus session context — registry provenance of
        the active schedules (``dispatch.report()``), the watchdog's
        drift/SLO report, and the lifecycle of every request the
        timeline names.  Called automatically when a postmortem-worthy
        event is recorded, and again at the end of the drain that
        dumped it (so the bundle on disk also reflects what recovery —
        e.g. a re-tuned commit — did); callable directly for ad-hoc
        snapshots."""
        rec = self._recorder
        if rec is None:
            return None
        context: Dict[str, Any] = {}
        if self.dispatch is not None:
            context["schedules"] = self.dispatch.report()
        if self._watchdog is not None:
            context["watchdog"] = self._watchdog.report()
        tel = self.telemetry
        if tel.enabled:
            lifecycles = {}
            for rid in rec.request_ids():
                r = tel.lifecycle.records.get(rid)
                if r is not None:
                    lifecycles[rid] = r.as_dict()
            context["request_lifecycles"] = lifecycles
        return rec.dump(reason, context)

    # ------------------------------------------------------ admission
    def submit(self, tokens, max_new_tokens: int,
               request_id: Optional[str] = None,
               extras: Optional[Dict[str, np.ndarray]] = None,
               deadline_s: Optional[float] = None) -> str:
        """Admit one request (a 1-D prompt); returns its id.

        ``deadline_s`` (submit → last token, seconds) overrides the
        session's ``request_deadline_s`` for this request; a blown
        deadline finishes it TIMED_OUT (partial tokens if decoding).
        """
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prompt = np.asarray(tokens, dtype=np.int32).reshape(-1)
        # Reject unbucketable prompts at admission: discovering them in
        # drain() would raise mid-stream with the request still at the
        # queue head, wedging every later request.
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if (self.bucket_lengths
                and prompt.size > max(self.bucket_lengths)):
            raise ValueError(
                f"prompt of length {prompt.size} exceeds the largest "
                f"bucket {max(self.bucket_lengths)}")
        rid = (request_id if request_id is not None
               else f"req-{next(_REQUEST_IDS)}")
        submitted_at = self._clock()
        self._queue.append(Request(
            tokens=prompt,
            max_new_tokens=int(max_new_tokens), request_id=rid,
            submitted_at=submitted_at, extras=extras,
            deadline_s=(deadline_s if deadline_s is not None
                        else self.request_deadline_s)))
        tel = self.telemetry
        if tel.enabled:
            tel.metrics.counter("serve.requests_submitted_total").inc()
            tel.lifecycle.submitted(rid, submitted_at)
            tel.tracer.async_begin("request", rid, request_id=rid)
        return rid

    def pending(self) -> int:
        """Requests queued but not yet served."""
        return len(self._queue)

    def cancel(self, request_id: str) -> bool:
        """Cancel a request.  Queued → finished CANCELLED immediately
        (empty tokens; the result is flushed by the next :meth:`drain`).
        Currently decoding → flagged, retired CANCELLED with its partial
        tokens at the next step boundary.  Unknown ids return False.
        """
        for i, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[i]
                self._finish_unadmitted(req, RequestState.CANCELLED,
                                        "cancelled while queued",
                                        self._done)
                return True
        if request_id in self._running:
            self._cancelled.add(request_id)
            return True
        return False

    # -------------------------------------- terminal-state accounting
    def _count_terminal(self, state: str) -> None:
        """Bump the per-terminal-state session counters."""
        if state == RequestState.REJECTED:
            self.stats.rejected += 1
        elif state == RequestState.TIMED_OUT:
            self.stats.timed_out += 1
        elif state == RequestState.CANCELLED:
            self.stats.cancelled += 1
        elif state == RequestState.FAILED:
            self.stats.failed += 1
        tel = self.telemetry
        if tel.enabled:
            tel.metrics.counter(
                f"serve.requests_{state.lower()}_total").inc()

    def _finish_unadmitted(self, req: Request, state: str, reason: str,
                           sink: List[RequestResult]) -> None:
        """Terminal result for a request that never reached a row."""
        log.warning("request %s finished %s without admission: %s",
                    req.request_id, state, reason)
        queue_s = self._clock() - req.submitted_at
        sink.append(RequestResult(
            request_id=req.request_id,
            tokens=np.zeros((0,), np.int32), bucket=_NULL_BUCKET,
            queue_s=queue_s, stats=None,
            state=state, reason=reason))
        self.stats.requests += 1
        self._count_terminal(state)
        if self._watchdog is not None:
            self._watchdog.note_queue(queue_s)
            self._watchdog.note_terminal(state == RequestState.COMPLETED)
        tel = self.telemetry
        if tel.enabled:
            tel.lifecycle.terminal(req.request_id, self._clock(),
                                   state, reason)
            tel.tracer.async_end("request", req.request_id, state=state)

    def _sweep_queue(self, sink: List[RequestResult]) -> None:
        """Queue-level terminal outcomes, applied at every admission
        boundary: client cancellations, blown deadlines, and
        ``max_queue_s`` load shedding (both → TIMED_OUT; sheds are also
        counted in ``stats.shed``)."""
        if not self._queue:
            return
        now = self._clock()
        kept: List[Request] = []
        for req in self._queue:
            wait = now - req.submitted_at
            if req.request_id in self._cancelled:
                self._cancelled.discard(req.request_id)
                self._finish_unadmitted(req, RequestState.CANCELLED,
                                        "cancelled while queued", sink)
            elif req.deadline_s is not None and wait > req.deadline_s:
                self._finish_unadmitted(
                    req, RequestState.TIMED_OUT,
                    f"deadline_s={req.deadline_s:g} blown after "
                    f"{wait:.3f}s in queue", sink)
            elif self.max_queue_s is not None and wait > self.max_queue_s:
                self.stats.shed += 1
                self._finish_unadmitted(
                    req, RequestState.TIMED_OUT,
                    f"shed: queued {wait:.3f}s > "
                    f"max_queue_s={self.max_queue_s:g}", sink)
            else:
                kept.append(req)
        self._queue = kept

    def _flush_done(self) -> List[RequestResult]:
        """Results finalised outside drain (e.g. queued cancellations)."""
        out, self._done = self._done, []
        return out

    def _straggler_event(self, event: Event) -> None:
        """StragglerMonitor hook: ledger the monitor's own structured
        event, optionally hold admission for the caller-returned number
        of boundaries."""
        self.stats.stragglers += 1
        self._record_event(event)
        if self.on_straggler is not None:
            hold = self.on_straggler(event)
            if isinstance(hold, int) and hold > 0:
                self._admission_hold = max(self._admission_hold, hold)

    # ------------------------------------------- degradable AOT compile
    def _aot_compile(self, fn, lower_args: tuple, *, what: str):
        """``fn.lower(*lower_args).compile()`` with ``compile_retries``
        retries under capped exponential backoff.  Returns
        ``(compiled_fn, True)`` on success or ``(fn, False)`` after the
        attempts are exhausted — the un-lowered jit fn still runs, so an
        AOT-only failure degrades performance, never correctness."""
        delay = self.compile_backoff_s
        last: Optional[Exception] = None
        tel = self.telemetry
        with self._span("serve.aot_compile", what=what):
            for attempt in range(1 + self.compile_retries):
                try:
                    if self._faults is not None:
                        self._faults.compile_fault(what)
                    return fn.lower(*lower_args).compile(), True
                except Exception as e:
                    last = e
                    log.warning("AOT compile of %s failed "
                                "(attempt %d/%d): %s", what, attempt + 1,
                                1 + self.compile_retries, e)
                    if attempt < self.compile_retries:
                        self.stats.compile_retries += 1
                        if tel.enabled:
                            tel.metrics.counter(
                                "serve.compile_retries_total").inc()
                        time.sleep(min(delay, 0.5))
                        delay *= 2
        self.stats.fallbacks += 1
        if tel.enabled:
            tel.metrics.counter("serve.aot_fallbacks_total").inc()
        self._event("compile_failure", what=what, error=repr(last))
        return fn, False

    def _build_step(self, jit_fn, lower_args: tuple, *, what: str,
                    ref_builder=None):
        """AOT-compile a step function, degrading gracefully.

        ``ref_builder`` (pallas buckets only) is a zero-arg callable
        returning the same-signature reference-backend jit fn; after a
        persistent AOT failure with ``fallback_backend="reference"`` the
        bucket's executable is rebuilt from it (``degraded`` flagged) —
        the same model on the XLA kernel path, whose logits agree with
        the pallas ones to rounding.  Otherwise the un-lowered fn is returned
        (``stats.fallbacks``).
        """
        fn, ok = self._aot_compile(jit_fn, lower_args, what=what)
        if ok or ref_builder is None \
                or self.fallback_backend != "reference":
            return fn
        log.warning("degrading %s to the reference backend", what)
        self.stats.degraded = True
        self.stats.degraded_buckets += 1
        self._event("degraded", what=what)
        ref_fn, _ = self._aot_compile(ref_builder(), lower_args,
                                      what=what + " [degraded]")
        return ref_fn

    # ------------------------------------------------------- batching
    def _prompt_bucket(self, request: Request) -> int:
        """Padded prompt length (the request's shape class)."""
        return bucket_length(len(request.tokens), self.bucket_lengths)

    def _bucket_step_time(self, bucket: Bucket) -> Optional[float]:
        """Expected decode-step seconds for a bucket's kernel shape:
        the dispatch service's measured time when observed (here or on
        any merged host), the cost model's best prediction when cold,
        None without a dispatch service."""
        if self.dispatch is None:
            return None
        from repro.runtime.serve_loop import serve_dispatch_problems
        cfg = self.model.cfg
        # Mirror run_batch's shape exactly (it widens the KV capacity
        # by the image tokens for VLMs) so the queried slot is the one
        # real traffic observes.
        total = bucket.total_len + (cfg.num_image_tokens
                                    if cfg.family == "vlm" else 0)
        kind, problem = serve_dispatch_problems(
            cfg, bucket.batch, bucket.prompt_len, total)["decode"]
        t = self.dispatch.measured_time(kind, problem)
        if t is None:
            predicted = self.dispatch.predicted(kind, problem)
            t = min(predicted) if predicted else None
        return t

    def _next_group(self) -> Tuple[List[Request], Bucket]:
        """Head-of-line shape class + its measured-best bucket."""
        head = self._queue[0]
        s_pad = self._prompt_bucket(head)
        same = [r for r in self._queue if self._prompt_bucket(r) == s_pad]
        # The new-token budget is bucketed too (power-of-2 grid — the
        # ``bucket_lengths`` grid describes *prompt* buckets), so
        # requests with different decode budgets share the decode
        # executable: only the KV/state capacity ``total_len`` is a
        # compiled dimension, the step count is a Python loop.
        cands = candidate_buckets([r.max_new_tokens for r in same],
                                  s_pad, self.batch_sizes)
        bucket, n_real = pick_bucket(cands, self._bucket_step_time)
        take = same[:n_real]
        taken = {id(r) for r in take}
        self._queue = [r for r in self._queue if id(r) not in taken]
        return take, bucket

    def _form_batch(self, group: List[Request], bucket: Bucket,
                    ) -> Dict[str, jnp.ndarray]:
        """Left-pad the group to the bucket shape (plus modality rows)."""
        cfg = self.model.cfg
        tokens = left_pad_prompts([r.tokens for r in group],
                                  bucket.prompt_len, self.pad_id)
        if bucket.batch > len(group):
            pad_rows = np.full((bucket.batch - len(group),
                                bucket.prompt_len), self.pad_id, np.int32)
            tokens = np.concatenate([tokens, pad_rows], axis=0)
        batch: Dict[str, jnp.ndarray] = {"tokens": jnp.asarray(tokens)}
        # Modality stubs: stack per-request extras, zero-fill the rest.
        def stack(name, shape, dtype=np.float32):
            """Stack one extras field across rows, zero-filling gaps."""
            rows = []
            for r in group:
                e = (r.extras or {}).get(name)
                rows.append(np.asarray(e, dtype=dtype) if e is not None
                            else np.zeros(shape, dtype))
            rows += [np.zeros(shape, dtype)] * (bucket.batch - len(group))
            return jnp.asarray(np.stack(rows, axis=0))

        if cfg.family == "audio":
            batch["frames"] = stack("frames",
                                    (cfg.encoder_seq, cfg.d_model))
        if cfg.family == "vlm":
            batch["image_embeds"] = stack(
                "image_embeds", (cfg.num_image_tokens, cfg.d_model))
        return batch

    def drain(self, on_step=None) -> List[RequestResult]:
        """Serve every queued request; returns per-request results in
        completion order.

        Dense/MoE/SSM families (greedy decoding) run the **in-flight
        engine** (:meth:`_drain_inflight`): requests are admitted,
        retired and their KV blocks recycled at decode *step*
        boundaries, so a short request never waits for a long batchmate
        and prefill interleaves with decode.  Other families (and
        sampled decoding) fall back to the batched path
        (:meth:`_drain_batched`), which serves whole groups at a time.

        ``on_step(info)`` — engine only — is called after every decode
        step with ``{"step", "active", "pending", "free_blocks"}``;
        tests (and latency probes) use it to submit mid-decode and to
        watch admission backpressure.

        Every result carries a terminal :class:`RequestState`; faults
        (poison rows, blown deadlines, never-fits rejections) finish the
        affected request and leave the rest of the stream running — see
        docs/SERVING.md §Failure semantics.
        """
        results = self._flush_done()
        if (self.model.cfg.family in ("dense", "moe", "ssm")
                and self.temperature <= 0.0):
            while self._queue:
                results.extend(self._drain_inflight(on_step))
            return results
        return results + self._drain_batched()

    def _drain_batched(self) -> List[RequestResult]:
        """Admission-granularity serving: form a group, run it to
        completion, repeat (the pre-engine behaviour; still the path for
        modality families the paged engine does not cover)."""
        results: List[RequestResult] = []
        masked = self.model.cfg.family in ("dense", "moe", "ssm")
        tel = self.telemetry
        while self._queue:
            # Queue-level outcomes only on this path: a whole group runs
            # to completion, so mid-decode timeouts/cancellation are an
            # engine capability (documented limitation).
            self._sweep_queue(results)
            if not self._queue:
                break
            group, bucket = self._next_group()
            t_start = time.perf_counter()
            waits = [t_start - r.submitted_at for r in group]
            batch = self._form_batch(group, bucket)
            steps = max(r.max_new_tokens for r in group)
            starts = None
            if masked:
                # Pad rows are fully masked (start == prompt_len): their
                # logits are garbage but finite, and they are discarded.
                starts = np.full((bucket.batch,), bucket.prompt_len,
                                 np.int32)
                starts[:len(group)] = prompt_starts(
                    [r.tokens for r in group], bucket.prompt_len)
            out, stats = self.run_batch(
                batch, max_new_tokens=steps,
                total_len=bucket.total_len,
                real_tokens=sum(r.max_new_tokens for r in group),
                seq_starts=starts)
            for i, r in enumerate(group):
                results.append(RequestResult(
                    request_id=r.request_id,
                    tokens=out[i, :r.max_new_tokens],
                    bucket=bucket, queue_s=waits[i], stats=stats))
            self.stats.requests += len(group)
            self.stats.queue_s.extend(waits)
            # TTFT on the batched path: the group's first tokens exist
            # once its shared prefill finishes.
            ttfts = [w + stats.prefill_s for w in waits]
            self.stats.ttft_s.extend(ttfts)
            if tel.enabled:
                t_done = self._clock()
                for r, w, tt in zip(group, waits, ttfts):
                    tel.metrics.histogram(
                        "serve.ttft_seconds").observe(tt)
                    tel.lifecycle.admitted(r.request_id,
                                           r.submitted_at + w)
                    tel.lifecycle.token(r.request_id,
                                        r.submitted_at + tt,
                                        n=r.max_new_tokens)
                    tel.lifecycle.terminal(r.request_id, t_done,
                                           RequestState.COMPLETED, None)
                    tel.tracer.async_end("request", r.request_id,
                                         state=RequestState.COMPLETED)
        return results

    # ------------------------------------------- in-flight engine
    def _drain_inflight(self, on_step=None) -> List[RequestResult]:
        """One engine *activation*: a fixed (rows, block-table) geometry
        serving requests at decode-step granularity until the queue and
        all rows are empty (or a request needs a wider geometry, which
        defers it to the next activation).

        Per step boundary the engine (1) retires finished rows and frees
        their KV blocks, (2) compacts the pool when fragmentation passes
        1/2, (3) admits queued requests FIFO while a row is free and the
        allocator can fit the request's whole ``prompt + budget - 1``
        footprint (strict FIFO: the first misfit stops admission — no
        overtaking), then (4) runs one decode step over all rows.
        Admission runs a batch-1 masked prefill through the shared
        executable cache and scatters the prompt KV (or SSM state) into
        the engine, so its greedy tokens equal running the request alone
        (logits agree to float reduction order).
        """
        from repro.runtime.serve_loop import (ServeStats,
                                              resolve_bundle_report,
                                              serve_dispatch_problems)
        model, params = self.model, self.params
        dispatch, backend = self.dispatch, self.backend
        cfg = model.cfg
        attn_family = cfg.family in ("dense", "moe")
        pallas = backend == "pallas"
        model_backend = "pallas" if pallas else "xla"

        # --- activation geometry: rows from the head-of-line class's
        # measured-best bucket, per-row capacity from the whole queue.
        head = self._queue[0]
        s_pad = self._prompt_bucket(head)
        budgets = [r.max_new_tokens for r in self._queue
                   if self._prompt_bucket(r) == s_pad]
        cands = candidate_buckets(budgets, s_pad, self.batch_sizes)
        picked, _ = pick_bucket(cands, self._bucket_step_time)
        rows_n = picked.batch
        cap = max(self._prompt_bucket(r)
                  + bucket_length(r.max_new_tokens)
                  for r in self._queue)
        cap = max(cap, picked.total_len)
        bs = self.kv_block_size
        max_blocks = blocks_needed(cap, bs)
        if attn_family:
            cap = max_blocks * bs   # gather extent == table reach
            n_blocks = (1 + rows_n * max_blocks
                        if self.kv_blocks is None else self.kv_blocks)
            alloc = BlockAllocator(n_blocks, bs)
            pool = model.init_paged_cache(n_blocks, bs)
            tables_np = np.zeros((rows_n, max_blocks), np.int32)
        else:
            alloc = None
            pool = model.init_cache(rows_n, cap)
            tables_np = None
        engine_bucket = Bucket(rows_n, s_pad, cap)
        act_stats = ServeStats(prefill_s=0.0, decode_s=0.0,
                               tokens_generated=0, backend=backend)
        deg0 = self.stats.degraded_buckets
        tel = self.telemetry
        act_span = self._begin("serve.activation", rows=int(rows_n),
                               prompt_bucket=int(s_pad))
        # Postmortem dump counts at drain entry: any reason dumped
        # during this drain is re-dumped once at the end, so the bundle
        # on disk also reflects what recovery did (e.g. the re-tuned
        # commit after a drift reopen).
        dumps0 = (dict(self._recorder.dumps)
                  if self._recorder is not None else {})

        problems = (serve_dispatch_problems(cfg, rows_n, s_pad, cap)
                    if dispatch is not None else {})
        dec = problems.get("decode")
        decode_bundle = None
        if dispatch is not None:
            dispatch.resolve(*dec)
            if pallas:
                decode_bundle = dispatch.schedule_bundle([dec])
        detail = ("paged", bs, max_blocks) if attn_family else None

        def decode_key(bundle) -> ExecKey:
            """Cache key of the engine's paged/recurrent decode step."""
            return ExecKey(cfg.name, "decode", rows_n, cap, bundle,
                           backend, detail)

        # --- per-prompt-bucket prefill executables (batch 1, shared
        # with every other engine activation and with run_batch).
        pf_bundles: Dict[int, Any] = {}

        def prefill_fn_for(p_len: int):
            """Cached batch-1 masked-prefill executable for a class."""
            bundle = None
            if dispatch is not None:
                kind, prob = serve_dispatch_problems(
                    cfg, 1, p_len, cap)["prefill"]
                if p_len not in pf_bundles:
                    dispatch.resolve(kind, prob)
                    pf_bundles[p_len] = (
                        dispatch.schedule_bundle([(kind, prob)])
                        if pallas else None)
                bundle = pf_bundles[p_len]
            key = ExecKey(cfg.name, "prefill", 1, p_len, bundle,
                          backend)

            def build():
                """AOT-lower the positional prefill wrapper (retry +
                per-bucket reference degradation on failure)."""
                def make(be, sched):
                    """Jit the prefill against one backend/schedules."""
                    def pf(p, b, st):
                        """Positional prefill (uniform cache sig)."""
                        return model.prefill(p, b, backend=be,
                                             schedules=sched,
                                             seq_starts=st)
                    return jax.jit(pf)
                lower_args = (
                    params,
                    {"tokens": jnp.zeros((1, p_len), jnp.int32)},
                    jnp.zeros((1,), jnp.int32))
                return self._build_step(
                    make(model_backend, bundle), lower_args,
                    what=f"prefill[b1,p{p_len}]",
                    ref_builder=(lambda: make("xla", None)) if pallas
                    else None)
            fn, _ = self._compile(key, build)
            return fn

        # --- mutable engine state (host side).
        row_req: List[Optional[Request]] = [None] * rows_n
        row_blocks: List[List[int]] = [[] for _ in range(rows_n)]
        row_remaining = [0] * rows_n
        row_out: List[List[int]] = [[] for _ in range(rows_n)]
        row_wait = [0.0] * rows_n
        # Terminal state a row retires with, when not COMPLETED (poison
        # rows, deadlines, cancellations): set before forcing
        # row_remaining to 0, consumed by retire().
        row_fate: Dict[int, Tuple[str, Optional[str]]] = {}
        pos_np = np.zeros((rows_n,), np.int32)
        tok_np = np.full((rows_n,), self.pad_id, np.int32)
        results: List[RequestResult] = []

        def bucket_entry():
            """Mutable per-bucket stats slot for this activation."""
            return self.stats.per_bucket.setdefault(
                engine_bucket,
                {"batches": 0, "tokens": 0, "decode_s": 0.0})

        def free_row_blocks(r: int, rid: str) -> None:
            """Release row r's pool blocks; an allocator invariant
            violation (double free) is contained as a recorded event —
            the row is retiring anyway and the rest of the pool stays
            live (not a drain abort)."""
            try:
                alloc.free(row_blocks[r])
                if (self._faults is not None
                        and self._faults.double_free(self._step_count)):
                    alloc.free(row_blocks[r])
            except ValueError as e:
                log.warning("allocator error retiring %s: %s", rid, e)
                self._event("allocator", step=self._step_count,
                            request_id=rid, error=str(e))
            tables_np[r, :] = 0

        def retire(r: int) -> None:
            """Finish row r in its terminal state (COMPLETED unless
            row_fate says otherwise), free its KV blocks, emit the
            result with the tokens actually delivered."""
            req = row_req[r]
            state, reason = row_fate.pop(
                r, (RequestState.COMPLETED, None))
            results.append(RequestResult(
                request_id=req.request_id,
                tokens=np.asarray(row_out[r], np.int32),
                bucket=engine_bucket, queue_s=row_wait[r],
                stats=act_stats, state=state, reason=reason))
            delivered = len(row_out[r])
            act_stats.tokens_generated += delivered
            self.stats.tokens_generated += delivered
            bucket_entry()["tokens"] += delivered
            self.stats.requests += 1
            self._count_terminal(state)
            self.stats.queue_s.append(row_wait[r])
            if self._watchdog is not None:
                self._watchdog.note_queue(row_wait[r])
                self._watchdog.note_terminal(
                    state == RequestState.COMPLETED)
            if tel.enabled:
                tel.lifecycle.terminal(req.request_id, self._clock(),
                                       state, reason)
                tel.tracer.async_end("request", req.request_id,
                                     state=state)
            self._running.discard(req.request_id)
            self._cancelled.discard(req.request_id)
            if attn_family and row_blocks[r]:
                free_row_blocks(r, req.request_id)
            row_req[r] = None
            row_blocks[r] = []
            row_out[r] = []
            pos_np[r] = 0
            tok_np[r] = self.pad_id

        def fail_admission(req: Request, r: int, reason: str) -> None:
            """Contain a prefill-time fault to the one request: free
            anything it allocated, emit a FAILED result, leave the row
            idle for the next admission."""
            log.warning("admission of %s failed: %s", req.request_id,
                        reason)
            self._event("admission_failure", step=self._step_count,
                        request_id=req.request_id, error=reason)
            if attn_family and row_blocks[r]:
                free_row_blocks(r, req.request_id)
                row_blocks[r] = []
            results.append(RequestResult(
                request_id=req.request_id,
                tokens=np.zeros((0,), np.int32), bucket=engine_bucket,
                queue_s=row_wait[r], stats=act_stats,
                state=RequestState.FAILED, reason=reason))
            self.stats.requests += 1
            self._count_terminal(RequestState.FAILED)
            if self._watchdog is not None:
                self._watchdog.note_terminal(False)
            if tel.enabled:
                tel.lifecycle.terminal(req.request_id, self._clock(),
                                       RequestState.FAILED, reason)
                tel.tracer.async_end("request", req.request_id,
                                     state=RequestState.FAILED)

        def admit(req: Request, r: int) -> bool:
            """Prefill req into row r and scatter its KV/state in;
            False when the prefill raised or produced non-finite logits
            (the request fails, the row stays usable)."""
            with self._span("serve.admit", request_id=req.request_id):
                return admit_phases(req, r)

        def admit_phases(req: Request, r: int) -> bool:
            """The body of :func:`admit`, one span per host phase:
            prepare, prefill, first token, placement."""
            nonlocal pool
            rid = req.request_id
            length = len(req.tokens)
            p_len = self._prompt_bucket(req)
            with self._span("serve.admit.prepare", request_id=rid):
                row_wait[r] = self._clock() - req.submitted_at
                if attn_family:
                    nb = blocks_needed(length + req.max_new_tokens - 1, bs)
                    row_blocks[r] = alloc.alloc(nb)
                    tables_np[r, :] = 0
                    tables_np[r, :nb] = row_blocks[r]
                toks = left_pad_prompts([req.tokens], p_len, self.pad_id)
                starts = jnp.asarray([p_len - length], jnp.int32)
                fn = prefill_fn_for(p_len)
                if dispatch is not None:
                    kind, prob = serve_dispatch_problems(
                        cfg, 1, p_len, cap)["prefill"]
                    dispatch.propose(kind, prob)
            t0 = time.perf_counter()
            try:
                with self._span("serve.prefill", request_id=rid,
                                prompt_len=int(p_len)):
                    logits, pcache = fn(params,
                                        {"tokens": jnp.asarray(toks)},
                                        starts)
                    jax.block_until_ready(logits)
            except Exception as e:
                # Kernel failure during prefill: this request only.
                fail_admission(req, r, f"prefill raised: {e}")
                return False
            dt = time.perf_counter() - t0
            if dispatch is not None:
                dispatch.observe(kind, prob, dt)
            act_stats.prefill_s += dt
            self.stats.prefill_s += dt
            with self._span("serve.admit.first_token", request_id=rid):
                finite = not self.nan_check or bool(
                    np.isfinite(np.asarray(logits[0, -1])).all())
                if finite:
                    first = int(np.asarray(
                        jnp.argmax(logits[0, -1], axis=-1)))
            if not finite:
                self.stats.poisoned_rows += 1
                fail_admission(req, r, "non-finite prefill logits")
                return False
            with self._span("serve.admit.place", request_id=rid):
                if attn_family:
                    # Scatter the row's real prompt KV into its pool
                    # blocks: positions 0..length-1 land in the first
                    # ceil(length/bs) blocks; the tail of the last block
                    # is zero-filled and overwritten by decode writes.
                    nbp = blocks_needed(length, bs)
                    idx = jnp.asarray(row_blocks[r][:nbp], jnp.int32)

                    def place(pool_t, pre):
                        """Scatter one K/V tensor into the row's blocks."""
                        real = pre[:, 0, :, p_len - length:, :].astype(
                            pool_t.dtype)
                        ln, hkv, _, hd = real.shape
                        padded = jnp.zeros((ln, hkv, nbp * bs, hd),
                                           pool_t.dtype)
                        padded = padded.at[:, :, :length, :].set(real)
                        blocked = padded.reshape(ln, hkv, nbp, bs, hd)
                        return pool_t.at[:, idx].set(
                            blocked.transpose(0, 2, 1, 3, 4))

                    pool = {"layers": {
                        "k": place(pool["layers"]["k"],
                                   pcache["layers"]["k"]),
                        "v": place(pool["layers"]["v"],
                                   pcache["layers"]["v"])}}
                else:
                    # Recurrent state is O(1) per row: write row r.
                    pool = jax.tree.map(
                        lambda e, s: e.at[:, r].set(
                            s[:, 0].astype(e.dtype)),
                        pool, pcache)
            row_req[r] = req
            row_out[r] = [first]
            row_remaining[r] = req.max_new_tokens - 1
            pos_np[r] = length
            tok_np[r] = first
            self._running.add(rid)
            self.stats.inflight_admissions += 1
            # TTFT: the engine's batch-1 prefill produced the first
            # token right here — submit -> now on the session clock.
            now = self._clock()
            self.stats.ttft_s.append(now - req.submitted_at)
            if self._watchdog is not None:
                self._watchdog.note_ttft(now - req.submitted_at)
            if tel.enabled:
                tel.metrics.counter(
                    "serve.inflight_admissions_total").inc()
                tel.metrics.histogram("serve.ttft_seconds").observe(
                    now - req.submitted_at)
                tel.lifecycle.admitted(rid, req.submitted_at + row_wait[r])
                tel.lifecycle.token(rid, now)
            return True

        step_fn = None
        cur_bundle = decode_bundle
        recompiles = 0
        recompile_s = 0.0
        switch_blocked = False

        def build_decode(bundle):
            """Builder factory for the engine decode step executable
            (retry + per-bucket reference degradation on AOT failure)."""
            def build():
                """AOT-lower the paged (attn) or batched (ssm) step."""
                if attn_family:
                    def make(be, sched):
                        """Jit the paged step for one backend."""
                        def step(p, c, t, pv, tb):
                            """Positional paged decode step (tables)."""
                            return model.decode_step(
                                p, c, t, pv, backend=be,
                                schedules=sched, block_tables=tb)
                        return jax.jit(step)
                    lower_args = (params, pool,
                                  jnp.asarray(tok_np)[:, None],
                                  jnp.asarray(pos_np),
                                  jnp.asarray(tables_np))
                else:
                    def make(be, sched):
                        """Jit the recurrent step for one backend."""
                        def step(p, c, t, pos):
                            """Positional recurrent decode step (named
                            like the paged one: ``jit_step``)."""
                            return model.decode_step(
                                p, c, t, pos, backend=be, schedules=sched)
                        return jax.jit(step)
                    lower_args = (params, pool,
                                  jnp.asarray(tok_np)[:, None],
                                  jnp.int32(0))
                return self._build_step(
                    make(model_backend, bundle), lower_args,
                    what=f"decode[b{rows_n},t{cap}]",
                    ref_builder=(lambda: make("xla", None)) if pallas
                    else None)
            return build

        def note_held(opened, reason: Optional[str]):
            """Keep, end or open the ``serve.queue.held`` span: open
            while the queue's head waits with a row free, for
            ``reason``; ended at the first boundary where it is
            admitted, no row is free, or the activation ends."""
            rid = self._queue[0].request_id if reason else None
            if opened is not None and opened.args == {
                    "reason": reason, "request_id": rid}:
                return opened
            self._end(opened)
            return (self._begin("serve.queue.held", tid=QUEUE_TID,
                                reason=reason, request_id=rid)
                    if reason else None)

        step_idx = 0
        inj_blocked = False
        hold_span = None
        while True:
            with self._span("serve.step", step=self._step_count):
                inj_blocked = False
                now = self._clock()
                for r in range(rows_n):
                    req = row_req[r]
                    if req is None:
                        continue
                    if row_remaining[r] <= 0:
                        retire(r)
                    elif req.request_id in self._cancelled:
                        row_fate[r] = (RequestState.CANCELLED,
                                       "cancelled mid-decode")
                        retire(r)
                    elif (req.deadline_s is not None
                            and now - req.submitted_at > req.deadline_s):
                        row_fate[r] = (
                            RequestState.TIMED_OUT,
                            f"deadline_s={req.deadline_s:g} blown "
                            f"mid-decode after {len(row_out[r])} tokens")
                        retire(r)
                if (attn_family and alloc.num_live
                        and alloc.fragmentation() > 0.5):
                    with self._span("serve.compact", step=self._step_count):
                        live = [row_blocks[r] for r in range(rows_n)
                                if row_blocks[r]]
                        perm, moved = alloc.compact_tables(tables_np, live)
                        if moved:
                            gather = jnp.asarray(perm)
                            pool = jax.tree.map(lambda p: p[:, gather], pool)
                            self.stats.compactions += 1
                self._sweep_queue(results)
                # Why the queue's head stays queued with a row free at
                # this boundary, if it does (the serve.queue.held span).
                held = None
                if self._admission_hold > 0:
                    # A straggler hook asked to shrink admission: skip this
                    # boundary, serve only the rows already in flight.
                    self._admission_hold -= 1
                    if self._queue and any(q is None for q in row_req):
                        held = "hold"
                else:
                    while self._queue:
                        free_rows = [r for r in range(rows_n)
                                     if row_req[r] is None]
                        if not free_rows:
                            break
                        nxt = self._queue[0]
                        if attn_family:
                            needed = (len(nxt.tokens)
                                      + nxt.max_new_tokens - 1)
                            nb = blocks_needed(needed, bs)
                            if nb > alloc.n_blocks - 1:
                                # Can NEVER fit this pool, even with every
                                # row idle: reject this request only and
                                # keep the engine running (pre-ISSUE-7 this
                                # raised RuntimeError out of drain()).
                                self._queue.pop(0)
                                self._finish_unadmitted(
                                    nxt, RequestState.REJECTED,
                                    f"needs {nb} KV blocks but the pool "
                                    f"holds {alloc.n_blocks - 1}; raise "
                                    f"kv_blocks", results)
                                continue
                            if needed > max_blocks * bs:
                                # Needs a wider table than this activation
                                # compiled: defer to the next activation,
                                # whose geometry is recomputed.
                                held = "table"
                                break
                            if (self._faults is not None
                                    and self._faults.alloc_blocked(
                                        self._step_count)):
                                self._event("alloc_exhausted",
                                            step=self._step_count)
                                inj_blocked = True
                                held = "fault"
                                break   # injected exhaustion: backpressure
                            if not alloc.can_fit(needed):
                                held = "pool"
                                break   # backpressure: wait for retirements
                        hold_span = note_held(hold_span, None)
                        if not admit(self._queue.pop(0), free_rows[0]):
                            continue    # admission fault: row still free
                hold_span = note_held(hold_span, held)
                active = [r for r in range(rows_n)
                          if row_req[r] is not None]
                if not active:
                    if inj_blocked and self._queue:
                        # Injected exhaustion with nothing in flight: count
                        # the stalled boundary so the finite fault window
                        # expires instead of wedging drain().
                        self._step_count += 1
                        continue
                    break
                if not any(row_remaining[r] > 0 for r in active):
                    continue    # budget-1 admissions retire at loop top
                if step_fn is None:
                    step_fn, _ = self._compile(decode_key(cur_bundle),
                                               build_decode(cur_bundle))
                if dispatch is not None:
                    kind, prob = dec
                    dispatch.propose(kind, prob)
                with self._span("serve.decode_step", step=self._step_count,
                                rows=len(active)):
                    t_step = time.perf_counter()
                    with self._span("serve.decode_step.upload"):
                        inputs = [jnp.asarray(tok_np)[:, None]]
                        if attn_family:
                            inputs += [jnp.asarray(pos_np),
                                       jnp.asarray(tables_np)]
                        else:
                            inputs.append(jnp.int32(0))
                    try:
                        with self._span("serve.decode_step.launch"):
                            lg, new_pool = step_fn(params, pool, *inputs)
                    except Exception as e:
                        # A step-level kernel failure is not attributable
                        # to one row: fail the rows that were in flight
                        # (their blocks free, partial tokens delivered)
                        # but keep the queue and the session alive —
                        # coarse isolation, not a drain abort.
                        log.warning("decode step raised: %s", e)
                        self._event("step_exception", step=self._step_count,
                                    error=str(e))
                        for r in active:
                            row_fate[r] = (RequestState.FAILED,
                                           f"decode step raised: {e}")
                            retire(r)
                        self._step_count += 1
                        continue
                    pool = new_pool
                    with self._span("serve.decode_step.fetch"):
                        if self._faults is not None:
                            for rr in self._faults.nan_rows(self._step_count):
                                if 0 <= rr < rows_n:
                                    lg = lg.at[rr, -1, :].set(jnp.nan)
                        new_tok = np.asarray(
                            jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32))
                    finite = None
                    if self.nan_check:
                        with self._span("serve.decode_step.check"):
                            finite = np.asarray(
                                jnp.all(jnp.isfinite(lg[:, -1]), axis=-1))
                    dt = time.perf_counter() - t_step
                # Injected slowdowns count once: the magnitude is read
                # here and reused by the straggler record and the
                # watchdog taps below (slow_extra_s logs its firing).
                extra = (self._faults.slow_extra_s(self._step_count)
                         if self._faults is not None else 0.0)
                act_stats.decode_s += dt
                self.stats.decode_s += dt
                bucket_entry()["decode_s"] += dt
                if tel.enabled:
                    tel.metrics.histogram(
                        "serve.decode_step_seconds").observe(dt)
                if dispatch is not None:
                    dispatch.observe(kind, prob, dt)
                    if self._watchdog is not None:
                        # Drift watch sees what the hardware delivered,
                        # injected slowdown included — dispatch medians
                        # stay clean (dt only), the watchdog judges the
                        # committed baseline against dt + extra.
                        self._watchdog.observe_slot(
                            dispatch.resolve(kind, prob), kind,
                            dt + extra, step=self._step_count)
                    if pallas and not switch_blocked:
                        committed = dispatch.committed(kind, prob)
                        if (committed is not None
                                and committed != cur_bundle.get(kind)):
                            new_bundle = cur_bundle.replace(
                                **{kind: committed})
                            new_key = decode_key(new_bundle)
                            if self.exec_cache.contains(new_key):
                                step_fn, _ = self._compile(
                                    new_key, build_decode(new_bundle))
                                cur_bundle = new_bundle
                                self.stats.free_switches += 1
                                self.stats.commits_seen += 1
                            elif recompiles < self.max_recompiles:
                                t_c = time.perf_counter()
                                step_fn, _ = self._compile(
                                    new_key, build_decode(new_bundle))
                                recompile_s += time.perf_counter() - t_c
                                recompiles += 1
                                cur_bundle = new_bundle
                                self.stats.commits_seen += 1
                            else:
                                switch_blocked = True
                                self.stats.commits_seen += 1
                t_tok = self._clock() if tel.enabled else 0.0
                for r in active:
                    if finite is not None and not finite[r]:
                        # Poison row: non-finite logits retire ONLY this
                        # row at the next boundary; batchmates are
                        # untouched (rows are independent — per-row
                        # positions/masks), so their tokens stay
                        # bit-identical to an uninjected run.
                        self.stats.poisoned_rows += 1
                        self._event("poison_row", step=self._step_count,
                                    request_id=row_req[r].request_id)
                        row_fate[r] = (
                            RequestState.FAILED,
                            f"non-finite logits at step {self._step_count}")
                        row_remaining[r] = 0
                        continue
                    if row_remaining[r] > 0:
                        t = int(new_tok[r])
                        row_out[r].append(t)
                        tok_np[r] = t
                        pos_np[r] += 1
                        row_remaining[r] -= 1
                        if tel.enabled:
                            tel.lifecycle.token(row_req[r].request_id, t_tok)
                            tel.lifecycle.decode_step(row_req[r].request_id)
                self.stats.steps += 1
                step_idx += 1
                self._straggler.record(self._step_count, dt + extra)
                wd = self._watchdog
                if wd is not None:
                    wd.note_step(tokens=len(active), dt=dt + extra)
                    wd.tick(self._step_count)
                rec = self._recorder
                if rec is not None:
                    rec.record_span("serve.decode_step",
                                    step=self._step_count,
                                    dur_s=dt + extra)
                    rec.record_metric("serve.tokens_generated_total",
                                      self.stats.tokens_generated)
                    if attn_family:
                        rec.note_allocator({
                            "blocks_total": alloc.n_blocks,
                            "blocks_live": alloc.num_live,
                            "blocks_free": alloc.num_free,
                            "fragmentation": alloc.fragmentation()})
                self._step_count += 1
                if tel.enabled and attn_family:
                    tel.metrics.gauge("serve.kv_blocks_live").set(
                        alloc.num_live)
                    tel.metrics.gauge("serve.kv_blocks_free").set(
                        alloc.num_free)
                    tel.metrics.gauge("serve.kv_fragmentation").set(
                        alloc.fragmentation())
                if on_step is not None:
                    on_step({"step": step_idx,
                             "active": [row_req[r].request_id
                                        for r in range(rows_n)
                                        if row_req[r] is not None],
                             "pending": len(self._queue),
                             "free_blocks": (alloc.num_free
                                             if attn_family else None)})

        note_held(hold_span, None)
        act_stats.recompiles = recompiles
        act_stats.recompile_s = recompile_s
        act_stats.degraded = self.stats.degraded_buckets > deg0
        if pallas and cur_bundle is not None:
            pf_b = next((b for b in pf_bundles.values()
                         if b is not None), cur_bundle)
            act_stats.schedules = dict(
                resolve_bundle_report(pf_b, cur_bundle))
        self.stats.batches += 1
        self.stats.recompiles += recompiles
        bucket_entry()["batches"] += 1
        self.stats.cache = self.exec_cache.stats()
        if tel.enabled:
            tel.metrics.set_gauges(
                {k: v for k, v in self.stats.cache.items()},
                prefix="serve.exec_cache.",
                help="executable-cache snapshot")
            self._straggler.export_metrics(tel.metrics)
        self._end(act_span, steps=int(step_idx))
        if self.registry is not None and step_idx:
            key = reg.RegistryKey.make(
                "serve_decode",
                {"arch": cfg.name, "batch": int(rows_n),
                 "prompt_len": int(s_pad),
                 "new_tokens": int(step_idx)},
                reg.runtime_fingerprint(), "measured")
            self.registry.record_measurement(
                key, {"type": "serve_decode", "arch": cfg.name,
                      "decode_tok_s": act_stats.decode_tok_s},
                act_stats.decode_s / max(step_idx, 1))
        if self._recorder is not None:
            for reason, n in sorted(self._recorder.dumps.items()):
                if n > dumps0.get(reason, 0):
                    self.dump_postmortem(reason)
        return results

    # ------------------------------------------------------ execution
    def _compile(self, key: ExecKey, builder) -> Tuple[Any, bool]:
        """Executable for key via the shared cache: ``(fn, was_hit)``."""
        fn, hit = self.exec_cache.get(key, builder)
        tel = self.telemetry
        if tel.enabled:
            tel.metrics.counter(
                "serve.exec_cache_hits_total" if hit
                else "serve.exec_cache_misses_total").inc()
        return fn, hit

    def run_batch(self, batch: Dict[str, jnp.ndarray], *,
                  max_new_tokens: int,
                  temperature: Optional[float] = None,
                  rng: Optional[jax.Array] = None,
                  total_len: Optional[int] = None,
                  real_tokens: Optional[int] = None,
                  seq_starts=None):
        """Greedy (or sampled) continuation of one pre-formed batch —
        the PR-4 ``generate`` body with the prefill/decode step
        functions behind the cross-request executable cache.

        Returns ``(tokens [B, max_new_tokens], ServeStats)``.
        ``total_len`` pads the KV/state capacity beyond
        ``prompt + max_new_tokens`` so differently-budgeted groups share
        the decode executable.  ``real_tokens`` is the number of tokens
        actually *delivered* to requests (drain() passes the group's
        budget sum): session-level throughput counts goodput, not
        pad-row or over-budget tokens, while the per-call ``ServeStats``
        keeps the executable's ``bsz * max_new_tokens`` accounting.

        ``seq_starts`` ([B] int32, optional) marks each row's first
        real token in a left-padded batch; pad tokens are then masked
        out of attention (and the SSM recurrence), making padded rows
        numerically equivalent to unpadded ones.  For the dense/MoE/SSM
        families the mask vector is ALWAYS threaded through the
        executables (zeros when not given) so cached step functions
        have one uniform signature; other families reject it.
        """
        from repro.runtime.serve_loop import (ServeStats, resolve_bundle_report,
                                              serve_dispatch_problems)
        model, params = self.model, self.params
        dispatch, backend = self.dispatch, self.backend
        cfg = model.cfg
        temperature = (self.temperature if temperature is None
                       else temperature)
        bsz, prompt_len = batch["tokens"].shape
        masked = cfg.family in ("dense", "moe", "ssm")
        if seq_starts is not None and not masked:
            raise ValueError(
                f"seq_starts is not supported for family {cfg.family!r}")
        starts = None
        if masked:
            starts = (jnp.zeros((bsz,), jnp.int32) if seq_starts is None
                      else jnp.asarray(seq_starts,
                                       jnp.int32).reshape(bsz))
        base_total = prompt_len + max_new_tokens
        if total_len is not None:
            if total_len < base_total:
                raise ValueError(
                    f"total_len {total_len} < prompt+new {base_total}")
            base_total = total_len
        total = base_total
        if cfg.family == "vlm":
            total += cfg.num_image_tokens
        pallas = backend == "pallas"
        model_backend = "pallas" if pallas else "xla"
        deg0 = self.stats.degraded_buckets

        problems = (serve_dispatch_problems(cfg, bsz, prompt_len, total)
                    if dispatch is not None else {})
        prefill_bundle = decode_bundle = None
        if dispatch is not None:
            # Resolve both shapes up front: warm registries answer with
            # zero cost-model evaluations; cold ones pay one batch sweep
            # here, not inside the timed loop.
            for kind, problem in problems.values():
                dispatch.resolve(kind, problem)
            if pallas:
                # One bundle per role: SSM prefill and decode share the
                # kernel kind ("ssm_scan") but are different shapes with
                # independently committed winners, so a single merged
                # bundle would let one silently shadow the other.
                prefill_bundle = dispatch.schedule_bundle(
                    [problems["prefill"]])
                decode_bundle = dispatch.schedule_bundle(
                    [problems["decode"]])
            dispatch.propose(*problems["prefill"])

        prefill_key = ExecKey(cfg.name, "prefill", bsz, prompt_len,
                              prefill_bundle, backend)

        def build_prefill():
            """AOT-lower the batched prefill (masked when starts set),
            with compile retry + per-bucket reference degradation."""
            # AOT-compile outside the timed region: the dispatch
            # observation (and prefill_s) should measure the step,
            # not XLA compilation.
            what = f"prefill[b{bsz},p{prompt_len}]"
            if starts is None:
                def make(be, sched):
                    """Jit the keyword prefill for one backend."""
                    return jax.jit(functools.partial(
                        model.prefill, backend=be, schedules=sched))
                return self._build_step(
                    make(model_backend, prefill_bundle),
                    (params, batch), what=what,
                    ref_builder=(lambda: make("xla", None)) if pallas
                    else None)

            def make(be, sched):
                """Jit the positional masked prefill for one backend."""
                def pf(p, b, st):
                    """Positional prefill (uniform cache sig)."""
                    return model.prefill(p, b, backend=be,
                                         schedules=sched,
                                         seq_starts=st)
                return jax.jit(pf)
            return self._build_step(
                make(model_backend, prefill_bundle),
                (params, batch, starts), what=what,
                ref_builder=(lambda: make("xla", None)) if pallas
                else None)

        prefill_fn, _ = self._compile(prefill_key, build_prefill)
        pf_span = self._begin("serve.prefill", batch=int(bsz),
                              prompt_len=int(prompt_len))
        t0 = time.time()
        logits, cache = (prefill_fn(params, batch) if starts is None
                         else prefill_fn(params, batch, starts))
        jax.block_until_ready(logits)
        prefill_exec_s = time.time() - t0
        if dispatch is not None:
            kind, problem = problems["prefill"]
            dispatch.observe(kind, problem, prefill_exec_s)
        # Grow caches to full capacity.
        full = model.init_cache(bsz, total)

        def fit(dst, src):
            """Copy the prefill cache into the full-capacity buffer."""
            if dst.shape == src.shape:
                return src.astype(dst.dtype)
            sl = tuple(slice(0, s) for s in src.shape)
            return dst.at[sl].set(src.astype(dst.dtype))

        cache = jax.tree.map(fit, full, cache)
        jax.block_until_ready(cache)
        prefill_s = time.time() - t0
        self._end(pf_span)

        def pick(lg, key):
            """Next token per row: greedy argmax or sampled."""
            if temperature <= 0.0:
                return jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, lg[:, -1] / temperature,
                                          -1).astype(jnp.int32)

        rng = rng if rng is not None else jax.random.key(0)
        rng, sub = jax.random.split(rng)
        tok = pick(logits, sub)
        out: List[np.ndarray] = [np.asarray(tok)]
        pos0 = prompt_len + (cfg.num_image_tokens
                             if cfg.family == "vlm" else 0)

        def decode_key(bundle) -> ExecKey:
            """Cache key of this batch shape's decode step."""
            return ExecKey(cfg.name, "decode", bsz, total, bundle,
                           backend)

        # Recurrent caches carry no pad entries after a masked prefill,
        # so only the attention families thread starts through decode.
        dec_starts = starts if cfg.family in ("dense", "moe") else None

        def build_decode(bundle):
            """Builder factory for the batched decode step executable."""
            def build():
                """AOT-lower the decode step (masked when starts set),
                with compile retry + per-bucket reference degradation."""
                # Same AOT treatment as prefill: keep compilation out
                # of the decode-step timings (a compile-inflated first
                # probe would poison the dispatcher's medians).
                what = f"decode[b{bsz},t{total}]"
                if dec_starts is None:
                    def make(be, sched):
                        """Jit the keyword decode step for one backend."""
                        return jax.jit(functools.partial(
                            model.decode_step, backend=be,
                            schedules=sched))
                    return self._build_step(
                        make(model_backend, bundle),
                        (params, cache, tok[:, None], jnp.int32(pos0)),
                        what=what,
                        ref_builder=(lambda: make("xla", None)) if pallas
                        else None)

                def make(be, sched):
                    """Jit the positional masked decode step."""
                    def st_step(p, c, t, pos, st):
                        """Positional decode step (starts threaded)."""
                        return model.decode_step(p, c, t, pos,
                                                 backend=be,
                                                 schedules=sched,
                                                 seq_starts=st)
                    return jax.jit(st_step)
                return self._build_step(
                    make(model_backend, bundle),
                    (params, cache, tok[:, None], jnp.int32(pos0),
                     dec_starts), what=what,
                    ref_builder=(lambda: make("xla", None)) if pallas
                    else None)
            return build

        step_fn = None
        if max_new_tokens > 1:
            step_fn, _ = self._compile(decode_key(decode_bundle),
                                       build_decode(decode_bundle))
        recompiles = 0
        recompile_s = 0.0
        switch_blocked = False  # budget spent on an uncached commit
        dec = problems.get("decode")

        dec_span = self._begin("serve.decode", batch=int(bsz),
                               steps=int(max_new_tokens - 1))
        t1 = time.time()
        for i in range(max_new_tokens - 1):
            t_step = time.perf_counter()
            if dispatch is not None:
                kind, problem = dec
                dispatch.propose(kind, problem)
            if dec_starts is None:
                lg, cache = step_fn(params, cache, tok[:, None],
                                    jnp.int32(pos0 + i))
            else:
                lg, cache = step_fn(params, cache, tok[:, None],
                                    jnp.int32(pos0 + i), dec_starts)
            rng, sub = jax.random.split(rng)
            tok = pick(lg, sub)
            out.append(np.asarray(tok))
            # np.asarray above synchronised the step; feed its wall time
            # to the straggler monitor (and the per-shape scheduler).
            dt = time.perf_counter() - t_step
            extra = (self._faults.slow_extra_s(self._step_count)
                     if self._faults is not None else 0.0)
            self._straggler.record(self._step_count, dt + extra)
            wd = self._watchdog
            if wd is not None:
                if dispatch is not None:
                    wd.observe_slot(dispatch.resolve(kind, problem),
                                    kind, dt + extra,
                                    step=self._step_count)
                wd.note_step(tokens=bsz, dt=dt + extra)
                wd.tick(self._step_count)
            if self._recorder is not None:
                self._recorder.record_span("serve.decode_step",
                                           step=self._step_count,
                                           dur_s=dt + extra)
            self._step_count += 1
            if dispatch is not None:
                dispatch.observe(kind, problem, dt)
                if pallas and not switch_blocked:
                    committed = dispatch.committed(kind, problem)
                    if (committed is not None
                            and committed != decode_bundle.get(kind)):
                        # The dispatcher just settled on a different
                        # winner than the step was compiled with.  If
                        # the matching executable is already in the
                        # session cache (another request compiled it),
                        # switch for free; otherwise re-AOT once, within
                        # the compile budget.  Either way the cache
                        # guarantees at most ONE compile per committed
                        # bundle session-wide — a commit is final, so
                        # every later request hits this entry.  Re-AOT
                        # wall time stays out of decode_s: throughput
                        # (and the CI-gated pallas-vs-reference ratio)
                        # must measure steps, not XLA compilation.
                        new_bundle = decode_bundle.replace(
                            **{kind: committed})
                        new_key = decode_key(new_bundle)
                        if self.exec_cache.contains(new_key):
                            step_fn, _ = self._compile(
                                new_key, build_decode(new_bundle))
                            decode_bundle = new_bundle
                            self.stats.free_switches += 1
                            self.stats.commits_seen += 1
                        elif recompiles < self.max_recompiles:
                            t_c = time.perf_counter()
                            step_fn, _ = self._compile(
                                new_key, build_decode(new_bundle))
                            recompile_s += time.perf_counter() - t_c
                            recompiles += 1
                            decode_bundle = new_bundle
                            self.stats.commits_seen += 1
                        else:
                            # Budget exhausted and the executable is
                            # not cached: a commit is final, so stop
                            # probing the cache on every remaining step
                            # of this call.
                            switch_blocked = True
                            self.stats.commits_seen += 1
        jax.block_until_ready(tok)
        decode_s = time.time() - t1 - recompile_s
        self._end(dec_span)
        report = None
        if prefill_bundle is not None:
            # Resolved once per (prefill, decode) bundle pair and
            # memoized — a pure cache-hit request no longer re-serialises
            # every schedule per call (profiled waste on short decode
            # budgets).
            report = dict(resolve_bundle_report(prefill_bundle,
                                                decode_bundle))
        stats = ServeStats(prefill_s=prefill_s, decode_s=decode_s,
                           tokens_generated=bsz * max_new_tokens,
                           backend=backend, recompiles=recompiles,
                           recompile_s=recompile_s, schedules=report,
                           degraded=self.stats.degraded_buckets > deg0)
        if self.registry is not None:
            key = reg.RegistryKey.make(
                "serve_decode",
                {"arch": cfg.name, "batch": int(bsz),
                 "prompt_len": int(prompt_len),
                 "new_tokens": int(max_new_tokens)},
                reg.runtime_fingerprint(), "measured")
            self.registry.record_measurement(
                key, {"type": "serve_decode", "arch": cfg.name,
                      "decode_tok_s": stats.decode_tok_s},
                decode_s / max(max_new_tokens, 1))

        # Fleet accounting (goodput: delivered tokens, not pad rows).
        delivered = (stats.tokens_generated if real_tokens is None
                     else real_tokens)
        bucket = Bucket(bsz, prompt_len, total)
        self.stats.batches += 1
        self.stats.prefill_s += prefill_s
        self.stats.decode_s += decode_s
        self.stats.tokens_generated += delivered
        self.stats.recompiles += recompiles
        entry = self.stats.per_bucket.setdefault(
            bucket, {"batches": 0, "tokens": 0, "decode_s": 0.0})
        entry["batches"] += 1
        entry["tokens"] += delivered
        entry["decode_s"] += decode_s
        self.stats.cache = self.exec_cache.stats()
        return np.stack(out, axis=1), stats


__all__ = ["Request", "RequestResult", "SessionStats", "ServeSession"]
