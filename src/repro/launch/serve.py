"""Serving launcher CLI: batched prefill + greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b-smoke \
        --batch 4 --prompt-len 16 --new-tokens 32

Session mode (persistent engine, queue -> bucket -> executable cache):

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b-smoke \
        --session --requests-file requests.jsonl --backend pallas --dispatch

``--requests-file`` is JSON-lines, one request per line:
``{"prompt_len": 12, "new_tokens": 8}`` (random tokens) or
``{"tokens": [1,2,3], "new_tokens": 8}``.  Without a file, ``--session``
synthesises a small mixed-shape stream.
"""
import argparse
import json


def _load_requests(path, n_default, prompt_len, new_tokens, vocab, rng):
    if path:
        reqs = []
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "tokens" in d:
                    toks = d["tokens"]
                else:
                    toks = rng.integers(0, vocab,
                                        int(d["prompt_len"])).tolist()
                reqs.append((toks, int(d.get("new_tokens", new_tokens))))
        return reqs
    # default synthetic mixed-shape stream around the CLI's shape args
    lens = [max(2, prompt_len // 2), prompt_len,
            max(3, (3 * prompt_len) // 4), prompt_len * 2]
    return [(rng.integers(0, vocab, lens[i % len(lens)]).tolist(),
             max(1, new_tokens // (1 + i % 2)))
            for i in range(n_default)]


def _exit_on_silent_failure(results, stats) -> None:
    """Exit nonzero when the fault-tolerance layer absorbed a failure
    that nobody injected: a FAILED request, a bucket degraded to the
    reference backend, or an AOT compile that fell back to the jit fn.
    Those keep a production stream alive, but a run that reports
    success must have run what it was asked to run."""
    failed = sum(r.state == "FAILED" for r in results)
    if failed or stats.degraded or stats.fallbacks:
        raise SystemExit(
            f"serve: {failed} failed request(s), "
            f"{stats.degraded_buckets} degraded bucket(s), "
            f"{stats.fallbacks} AOT fallback(s) with no --inject-fault")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--registry", default=None,
                    help="tuning-registry path: measured decode "
                         "throughput is written back")
    ap.add_argument("--dispatch", action="store_true",
                    help="route prefill/decode through the adaptive "
                         "dispatch service (per-shape tune -> select -> "
                         "observe; winners written to the registry)")
    ap.add_argument("--backend", default="reference",
                    choices=("reference", "pallas"),
                    help="'reference' lowers the model through XLA as-is;"
                         " 'pallas' AOT-compiles prefill/decode with the "
                         "dispatch service's committed schedules as "
                         "static arguments (re-AOT on commit, bounded "
                         "by --max-recompiles)")
    ap.add_argument("--max-recompiles", type=int, default=1,
                    help="compile budget: max mid-stream decode re-AOTs "
                         "after a dispatcher commit")
    ap.add_argument("--session", action="store_true",
                    help="serve through a persistent ServeSession "
                         "(admission queue, dispatch-aware bucketing, "
                         "cross-request executable cache)")
    ap.add_argument("--requests-file", default=None,
                    help="JSONL request stream for --session (one "
                         "{'prompt_len'|'tokens', 'new_tokens'} per "
                         "line); default: a synthetic mixed stream")
    ap.add_argument("--num-requests", type=int, default=12,
                    help="size of the synthetic --session stream when "
                         "no --requests-file is given")
    ap.add_argument("--batch-sizes", default="1,2,4,8",
                    help="allowed continuous-batching batch dims "
                         "(--session)")
    ap.add_argument("--cache-capacity", type=int, default=16,
                    help="LRU bound on cached compiled executables "
                         "(--session)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="token slots per paged-KV pool block "
                         "(--session, in-flight engine)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged-KV pool size in blocks; default sizes "
                         "the pool so every engine row reaches full "
                         "capacity — smaller values throttle admission "
                         "(--session)")
    ap.add_argument("--request-deadline-s", type=float, default=None,
                    help="per-request wall-clock budget from submit; "
                         "blown requests finish TIMED_OUT with partial "
                         "tokens instead of stalling the stream "
                         "(--session)")
    ap.add_argument("--max-queue-s", type=float, default=None,
                    help="load shedding: requests queued longer than "
                         "this are shed (TIMED_OUT) before admission "
                         "(--session)")
    ap.add_argument("--fallback-backend", default="reference",
                    choices=("reference", "none"),
                    help="after pallas AOT retries are exhausted, "
                         "'reference' degrades that bucket to the XLA "
                         "reference backend; 'none' keeps the un-lowered "
                         "pallas fn (--session)")
    ap.add_argument("--inject-fault", action="append", default=None,
                    metavar="KIND@STEP",
                    help="dev-only deterministic fault injection "
                         "(kind@step[xTIMES][.ROW]; kinds: compile, nan, "
                         "alloc, slow, doublefree); repeatable "
                         "(--session)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event / Perfetto JSON of "
                         "the run (engine spans + per-request tracks) "
                         "to PATH")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry in Prometheus text "
                         "exposition format to PATH")
    ap.add_argument("--watchdog", action="store_true",
                    help="enable the performance watchdog: online drift "
                         "detection over the dispatch slots (sustained "
                         "breaches reopen the slot for re-tuning) plus "
                         "SLO burn tracking (--session)")
    ap.add_argument("--slo", action="append", default=None,
                    metavar="SPEC",
                    help="declarative SLO, repeatable (implies "
                         "--watchdog): ttft_p95<=S, queue_p95<=S, "
                         "tok_s>=R, error_rate<=F (--session)")
    ap.add_argument("--postmortem-dir", default=None, metavar="DIR",
                    help="enable the flight recorder: faults, SLO "
                         "pages, and drift alarms dump a deterministic "
                         "postmortem-<reason>.json bundle into DIR "
                         "(--session)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.registry import TuningRegistry
    from repro.models import build_model
    from repro.runtime.serve_loop import generate

    cfg = get_config(args.arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (args.batch, cfg.encoder_seq, cfg.d_model),
            jnp.float32)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            jax.random.key(2),
            (args.batch, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    registry = TuningRegistry(args.registry) if args.registry else None
    telemetry = None
    if args.trace_out or args.metrics_out:
        from repro.obs import Telemetry
        telemetry = Telemetry()
    dispatch = None
    if args.dispatch:
        from repro.runtime.dispatch import DispatchService, \
            get_dispatch_service
        if registry is not None:
            kw = ({"metrics": telemetry.metrics,
                   "tracer": telemetry.tracer}
                  if telemetry is not None else {})
            dispatch = DispatchService(registry, **kw)
        else:
            dispatch = get_dispatch_service()
    if args.backend == "pallas" and dispatch is None:
        from repro.runtime.dispatch import get_dispatch_service
        dispatch = get_dispatch_service()

    def _write_telemetry():
        if telemetry is None:
            return
        if args.trace_out:
            telemetry.tracer.write(args.trace_out)
            print(f"trace written to {args.trace_out} "
                  f"(load in Perfetto or chrome://tracing)")
        if args.metrics_out:
            telemetry.metrics.write_prometheus(args.metrics_out)
            print(f"metrics written to {args.metrics_out}")

    if args.session:
        import numpy as np
        from repro.serving import FaultInjector, ServeSession
        faults = (FaultInjector.from_strings(args.inject_fault)
                  if args.inject_fault else None)
        watchdog = None
        if args.watchdog or args.slo:
            from repro.obs import PerformanceWatchdog
            watchdog = PerformanceWatchdog(args.slo or ())
        recorder = None
        if args.postmortem_dir:
            from repro.obs import FlightRecorder
            recorder = FlightRecorder(out_dir=args.postmortem_dir)
        session = ServeSession(
            model, params, dispatch=dispatch, backend=args.backend,
            registry=registry, max_recompiles=args.max_recompiles,
            cache_capacity=args.cache_capacity,
            batch_sizes=tuple(int(b) for b in
                              args.batch_sizes.split(",") if b.strip()),
            temperature=args.temperature,
            kv_block_size=args.kv_block_size,
            kv_blocks=args.kv_blocks,
            request_deadline_s=args.request_deadline_s,
            max_queue_s=args.max_queue_s,
            fallback_backend=args.fallback_backend,
            faults=faults, telemetry=telemetry,
            watchdog=watchdog, recorder=recorder)
        rng = np.random.default_rng(0)
        reqs = _load_requests(args.requests_file, args.num_requests,
                              args.prompt_len, args.new_tokens,
                              cfg.vocab_size, rng)
        for toks, budget in reqs:
            session.submit(toks, max_new_tokens=budget)
        results = session.drain()
        for r in results:
            tail = "" if r.state == "COMPLETED" else (
                f" [{r.state}: {r.reason}]")
            print(f"{r.request_id}: {len(r.tokens)} tokens via "
                  f"bucket(b={r.bucket.batch}, p={r.bucket.prompt_len}, "
                  f"t={r.bucket.total_len}); queued {r.queue_s*1e3:.1f}ms"
                  f"{tail}")
        summary = session.stats.to_dict()
        if summary["steps"]:
            print(f"\nengine: {summary['steps']} decode steps, "
                  f"{summary['inflight_admissions']} in-flight "
                  f"admissions, {summary['compactions']} pool "
                  f"compactions")
        print(f"\nsession: {summary['requests']} requests in "
              f"{summary['batches']} batches; "
              f"{summary['decode_tok_s']:.0f} tok/s; cache hit rate "
              f"{summary['cache_hit_rate']:.2f} "
              f"({summary['cache']['compiles']} compiles, "
              f"{summary['cache']['evictions']} evictions); re-AOTs "
              f"{summary['recompiles']}; queue p50/p95 "
              f"{summary['queue_p50_s']*1e3:.1f}/"
              f"{summary['queue_p95_s']*1e3:.1f}ms")
        for name, b in summary["buckets"].items():
            print(f"  bucket {name}: {b['tok_s']:.0f} tok/s over "
                  f"{int(b['batches'])} batches")
        # Fault/degradation line derived from the unified event log
        # (repro.obs.events) — the same records exported as telemetry.
        from repro.obs.events import format_event_summary
        events = session.stats.events
        if events or summary.get("degraded"):
            print(format_event_summary(
                events,
                degraded=[e.what for e in events
                          if e.kind == "degraded"]))
        if dispatch is not None:
            for entry in dispatch.report().values():
                committed = entry["committed"]
                print(f"dispatch {entry['kind']}: "
                      f"obs={entry['observations']} "
                      f"committed={committed if committed else '(probing)'}")
        if watchdog is not None:
            wrep = watchdog.report()
            pages = sum(int(s["pages"]) for s in wrep["slo"].values())
            line = (f"watchdog: drift={wrep['drifts']} "
                    f"reopens={wrep['reopens']}/{wrep['retune_budget']} "
                    f"slo_pages={pages}")
            for name, s in sorted(wrep["slo"].items()):
                line += (f" | {s['spec']}: burn "
                         f"{s['burn_short']:.2f}/{s['burn_long']:.2f}"
                         f"{' PAGED' if s['paged'] else ''}")
            print(line)
        if recorder is not None and recorder.dumps:
            print("postmortems: " + ", ".join(
                f"{reason} x{n}"
                for reason, n in sorted(recorder.dumps.items()))
                + f" (in {recorder.out_dir}/)")
        _write_telemetry()
        if not args.inject_fault:
            _exit_on_silent_failure(results, session.stats)
        return

    from repro.serving import ServeSession
    session = ServeSession(model, params, dispatch=dispatch,
                           backend=args.backend, registry=registry,
                           max_recompiles=args.max_recompiles)
    out, stats = generate(model, params, batch,
                          max_new_tokens=args.new_tokens,
                          temperature=args.temperature, session=session)
    print(f"generated {out.shape}; prefill {stats.prefill_s*1e3:.1f}ms; "
          f"decode {stats.decode_tok_s:.0f} tok/s; "
          f"backend={stats.backend} recompiles={stats.recompiles}")
    if stats.schedules is not None:
        live = {k: v for k, v in stats.schedules.items() if v is not None}
        print(f"compiled-step schedules: {live}")
    if dispatch is not None:
        for entry in dispatch.report().values():
            committed = entry["committed"]
            print(f"dispatch {entry['kind']}: obs={entry['observations']}"
                  f" committed={committed if committed else '(probing)'}")
    _write_telemetry()
    _exit_on_silent_failure([], session.stats)


if __name__ == "__main__":
    main()
