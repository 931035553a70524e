"""Drives the serving engine through one cell: build, warm, measure, check.

The system under test is ``repro.serving.ServeSession`` as a deployment
runs it (``backend`` and dispatch from the configuration's ``engine``
block), fed by an open loop: every request is submitted when it is due,
between the engine's decode steps (through ``drain(on_step=...)``) or,
with the engine idle, by waiting for the next arrival.  Every latency is
timed from the request's due time, on the host's ``perf_counter``, the
clock the engine's lifecycle log also uses.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from harness.traffic import Arrival


@dataclasses.dataclass
class Served:
    """One request of the window, as the engine served it."""

    due: float                       # perf_counter time it was due
    prompt: np.ndarray
    max_new_tokens: int
    rid: Optional[str] = None
    submitted: Optional[float] = None
    admitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    state: Optional[str] = None
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Window:
    """What the measured window produced."""

    t0: float
    t_end: float
    requests: List[Served]
    compiles: int                    # XLA compiles and cache loads inside
    decode_steps: int
    late_s: float                    # latest submission past its due time
    # ("drain", t) when the loop hands the engine work, ("step", t) after
    # each decode step: the engine's stalls lie between them.
    marks: List[tuple] = dataclasses.field(default_factory=list)


class CompileCounter:
    """Counts every executable JAX builds: backend compiles and loads
    from the persistent compilation cache."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_: Any) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event: str, **_: Any) -> None:
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def pow2_bucket(n: int, align: int = 8) -> int:
    """The engine's budget bucket: the smallest power of two >= n,
    floored at ``align``."""
    return max(align, 1 << max(0, math.ceil(math.log2(n))))


def prompt_bucket(n: int, buckets) -> int:
    """The smallest configured prompt bucket that holds ``n`` tokens."""
    return min(b for b in buckets if b >= n)


class ServeCell:
    """The engine of one serving cell, its weights and its traffic."""

    def __init__(self, sizes: Dict[str, Any], ref, *, seed: int,
                 out_dir, log=print):
        import jax
        from repro.configs.base import ModelConfig
        from repro.core.registry import TuningRegistry
        from repro.models import build_model
        from repro.obs import Telemetry
        from repro.obs.metrics import MetricsRegistry
        from repro.obs.trace import SpanTracer
        from repro.runtime.dispatch import DispatchService
        from repro.serving import ServeSession

        self.sizes, self.ref, self.log = sizes, ref, log
        self.engine = dict(sizes["engine"])
        self.cfg = ModelConfig(name=sizes["name"], source=sizes["source"],
                               **ref.program_fields(sizes))
        self.model = build_model(self.cfg)
        self.params = ref.make_params(sizes, seed)
        jax.block_until_ready(self.params)
        want = self.model.abstract_params()
        got = jax.eval_shape(lambda p: p, self.params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        out_dir.mkdir(parents=True, exist_ok=True)
        registry = out_dir / "registry.jsonl"
        registry.unlink(missing_ok=True)
        # The tracer's origin, bracketed, to put its spans on the clock
        # of the device trace.
        before = time.perf_counter()
        tracer = SpanTracer(clock=time.perf_counter)
        self.tracer_t0 = 0.5 * (before + time.perf_counter())
        self.telemetry = Telemetry(metrics=MetricsRegistry(), tracer=tracer)
        self.dispatch = (DispatchService(TuningRegistry(str(registry)),
                                         metrics=MetricsRegistry())
                         if self.engine.get("dispatch", True) else None)
        self.session = ServeSession(
            self.model, self.params, backend=self.engine["backend"],
            dispatch=self.dispatch,
            batch_sizes=(int(self.engine["rows"]),),
            bucket_lengths=tuple(self.engine["prompt_buckets"]),
            kv_block_size=int(self.engine.get("kv_block_size", 16)),
            kv_blocks=self.engine.get("kv_blocks"),
            cache_capacity=int(self.engine.get("cache_capacity", 64)),
            telemetry=self.telemetry)
        self.attention = self.cfg.family in ("dense", "moe")
        self._rng = np.random.default_rng(0)

    # ------------------------------------------------------------ warm-up
    def _prompt(self, n: int) -> np.ndarray:
        return self._rng.integers(1, self.cfg.vocab_size, n).astype(np.int32)

    def _problems(self, batch: int, p_len: int, cap: int):
        from repro.runtime.serve_loop import serve_dispatch_problems
        return serve_dispatch_problems(self.cfg, batch, p_len, cap)

    def _decode_ready(self, p_len: int, cap: int) -> bool:
        """The decode slot of this geometry has committed and the engine
        holds the executable of the committed schedules."""
        if self.dispatch is None:
            return any(k.role == "decode" and k.length == cap
                       for k, _ in self.session.exec_cache.items())
        dec = self._problems(int(self.engine["rows"]), p_len, cap)["decode"]
        if self.dispatch.committed(*dec) is None:
            return False
        bundle = self.dispatch.schedule_bundle([dec])
        if self.engine["backend"] != "pallas":
            bundle = None
        return any(k.role == "decode" and k.length == cap
                   and k.schedules == bundle
                   for k, _ in self.session.exec_cache.items())

    def _prefill_committed(self, p_len: int) -> bool:
        if self.dispatch is None:
            return True
        kind, prob = self._problems(1, p_len, p_len)["prefill"]
        return self.dispatch.committed(kind, prob) is not None

    def warm(self, arrivals: List[Arrival], counter: CompileCounter,
             max_rounds: int = 12) -> None:
        """Build everything the window can reach, so that it compiles
        nothing: every prompt length's admission (its prefill executable
        and the host-side placement of its KV or state), the dispatch
        commit of every prefill and decode slot, and every decode
        geometry (rows x capacity) the queue can lead the engine to."""
        sess, eng = self.session, self.engine
        buckets = sorted(eng["prompt_buckets"])
        # Attention engines need a fixed pool (``kv_blocks``): the shapes
        # of the pool's placement and compaction programs follow it.
        bs = int(eng.get("kv_block_size", 16))
        lengths = sorted({len(a.prompt) for a in arrivals})
        p_used = sorted({prompt_bucket(n, buckets) for n in lengths})
        nb_used = sorted({pow2_bucket(a.max_new_tokens) for a in arrivals})

        def cap_of(p_len: int, nb: int) -> int:
            cap = p_len + nb
            return -(-cap // bs) * bs if self.attention else cap

        # Admissions of every prompt length, until the prefill slots have
        # committed and a pass builds nothing new.
        for i in range(max_rounds):
            before = counter.count
            counter.active = True
            for n in lengths:
                sess.submit(self._prompt(n), max_new_tokens=1)
            sess.drain()
            counter.active = False
            self.log(f"warm-up: prompt lengths pass {i + 1}: "
                     f"{counter.count - before} compiles")
            if (i and counter.count == before
                    and all(self._prefill_committed(p) for p in p_used)):
                break
        # A compaction of the paged pool: a long prompt with a short
        # answer retires first and leaves the pool's bottom empty under a
        # live request, so the engine re-packs the pool (an eager gather
        # over the whole pool, built once per pool shape).
        if self.attention and max(nb_used) > 8:
            n0 = sess.stats.compactions
            sess.submit(self._prompt(max(lengths)), max_new_tokens=8)
            rid = sess.submit(self._prompt(min(lengths)),
                              max_new_tokens=max(nb_used))

            def stop(_info):
                if sess.stats.compactions > n0:
                    sess.cancel(rid)
            sess.drain(on_step=stop)
            if sess.stats.compactions == n0:
                self.log("warm-up: no compaction happened")
        # Every decode geometry, until its slot commits and the committed
        # executable is built.
        done = set()
        for p_len in p_used:
            for nb in nb_used:
                cap = cap_of(p_len, nb)
                if cap in done:
                    continue
                done.add(cap)
                for _ in range(max_rounds):
                    rid = sess.submit(self._prompt(p_len), max_new_tokens=nb)

                    def stop(_info, rid=rid):
                        if self._decode_ready(p_len, cap):
                            sess.cancel(rid)
                    sess.drain(on_step=stop)
                    if self._decode_ready(p_len, cap):
                        break
                else:
                    self.log(f"warm-up: decode geometry p{p_len}/t{cap} "
                             f"did not commit in {max_rounds} rounds")

    # ------------------------------------------------------------ window
    def measure(self, arrivals: List[Arrival], seconds: float,
                counter: CompileCounter, annotate=None) -> Window:
        """Offer ``arrivals`` for ``seconds``; at the end of the window
        cancel what is left, so the run stops there."""
        import jax
        sess, lc = self.session, self.telemetry.lifecycle.records
        annotate = annotate or (lambda _name: contextlib.nullcontext())
        reqs = [Served(due=0.0, prompt=a.prompt,
                       max_new_tokens=a.max_new_tokens) for a in arrivals]
        by_rid: Dict[str, Served] = {}
        last_seen: Dict[str, float] = {}
        state = {"next": 0, "closed": False, "late": 0.0, "steps": 0}
        marks: List[tuple] = []
        results = []

        def submit_due(now: float) -> None:
            i = state["next"]
            while i < len(reqs) and reqs[i].due <= now:
                r = reqs[i]
                r.rid = sess.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                r.submitted = time.perf_counter()
                state["late"] = max(state["late"], r.submitted - r.due)
                by_rid[r.rid] = r
                i += 1
            state["next"] = i

        def close() -> None:
            state["closed"] = True
            for r in by_rid.values():
                if r.state is None:
                    sess.cancel(r.rid)

        def on_step(info) -> None:
            now = time.perf_counter()
            state["steps"] += 1
            marks.append(("step", now))
            for rid in info["active"]:
                rec = lc.get(rid)
                if rec is None or rec.last_token_ts is None:
                    continue
                # The first token (from the admission's prefill) is read
                # from the lifecycle log at the end; steps add the rest.
                last_seen.setdefault(rid, rec.first_token_ts)
                if last_seen[rid] != rec.last_token_ts:
                    last_seen[rid] = rec.last_token_ts
                    by_rid[rid].token_times.append(rec.last_token_ts)
            if state["closed"]:
                return
            if now >= t_end:
                close()
            else:
                with annotate("bench.submit"):
                    submit_due(now)

        t0 = time.perf_counter()
        t_end = t0 + seconds
        for r, a in zip(reqs, arrivals):
            r.due = t0 + a.due_s
        counter.active = True
        c0 = counter.count
        log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        with annotate("bench.window"):
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                submit_due(now)
                if sess.pending():
                    marks.append(("drain", time.perf_counter()))
                    with annotate("bench.drain"):
                        results += sess.drain(on_step=on_step)
                elif state["next"] < len(reqs):
                    with annotate("bench.wait_arrival"):
                        time.sleep(max(0.0, min(reqs[state["next"]].due,
                                                t_end) - time.perf_counter()))
                else:
                    with annotate("bench.wait_arrival"):
                        time.sleep(max(0.0, t_end - time.perf_counter()))
        compiles = counter.count - c0
        counter.active = False
        jax.config.update("jax_log_compiles", log_compiles)
        if not state["closed"]:
            close()
        results += sess.drain()
        for res in results:
            r = by_rid.get(res.request_id)
            if r is not None:
                r.state, r.tokens = res.state, np.asarray(res.tokens)
        for r in by_rid.values():
            rec = lc.get(r.rid)
            if rec is not None:
                r.admitted = rec.admitted_ts
                if rec.first_token_ts is not None:
                    r.token_times.insert(0, rec.first_token_ts)
        return Window(t0=t0, t_end=t_end, requests=reqs, compiles=compiles,
                      decode_steps=state["steps"], late_s=state["late"],
                      marks=marks)

    def engine_spans(self) -> List[Dict[str, Any]]:
        """The engine's own spans (name, start, end on perf_counter)."""
        out = []
        for e in self.telemetry.tracer.events:
            if e.get("ph") == "X":
                start = self.tracer_t0 + e["ts"] / 1e6
                out.append({"name": e["name"], "start": start,
                            "end": start + e["dur"] / 1e6})
        return out

    def free_engine(self) -> None:
        """Drop the engine and its KV pools; the weights stay for the
        reference."""
        self.session = None
        self.dispatch = None
        gc.collect()


def decode_steps(win: Window, lo: float, hi: float):
    """The engine's decode steps whose tokens reached the host in
    [lo, hi]: one list per step of (request, context), where context is
    the number of positions the step's attention read for that row
    (prompt plus the tokens before the one produced)."""
    steps: Dict[float, list] = {}
    for r in win.requests:
        for k, t in enumerate(r.token_times[1:], start=1):
            if lo <= t <= hi:
                steps.setdefault(t, []).append((r, len(r.prompt) + k))
    return [steps[t] for t in sorted(steps)]


def admissions(win: Window, lo: float, hi: float) -> List[Served]:
    """Requests whose admission (their prefill) began in [lo, hi]."""
    return [r for r in win.requests
            if r.admitted is not None and lo <= r.admitted <= hi]
