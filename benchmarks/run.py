"""Benchmark harness — one module per thesis table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping (DESIGN.md §6):

    bench_loop_orders      Fig 4.2/4.3/4.5   720-perm signatures
    bench_top_candidates   Fig 4.7-4.10      static candidates, 1t/8t
    bench_cache_hierarchy  Fig 5.1           rank stability vs caches
    bench_parallel         Fig 4.4/5.2       rank stability vs threads
    bench_combinations     Fig 5.3/5.4       top-K pairs, random samples
    bench_sparsity         Fig 6.2           dense vs sparse kernels
    bench_tile_swap        Fig 6.3/6.4       compute/cache resource split
    bench_adaptive         Fig 6.5           micro-profiling steadiness
    bench_validation       Fig 2.3/6.1       fast-vs-exact simulator
    bench_roofline         (TPU adaptation)  dry-run roofline summary
    bench_registry         (persistence)     warm-vs-cold cached tuning
    bench_serve            (serving session) mixed-stream cache reuse

``--quick`` (or env REPRO_BENCH_QUICK=1) shrinks every bench to smoke
size — tiny shapes, truncated design spaces — and any bench failure makes
the process exit nonzero, so CI can gate on it.

Headline numbers each bench records (sweep wall-time, evals/sec,
warm-vs-cold ratio, batch-vs-scalar speedup) are written to
``BENCH_sweep.json`` (``--bench-json`` to relocate, empty string to
disable) so the perf trajectory is machine-readable across PRs; CI
uploads it as an artifact and fails if the batch-engine speedup regresses
below 5x.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

MODULES = [
    "loop_orders", "top_candidates", "cache_hierarchy", "parallel",
    "combinations", "sparsity", "tile_swap", "adaptive", "validation",
    "roofline", "registry", "serve", "faults", "ecm",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.run")
    ap.add_argument("benches", nargs="*", default=[],
                    help=f"subset to run (default: all of {MODULES})")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny shapes, truncated spaces")
    ap.add_argument("--bench-json", default="BENCH_sweep.json",
                    help="where to write the machine-readable metric "
                         "summary ('' disables)")
    ap.add_argument("--adaptive-json", default="BENCH_adaptive.json",
                    help="where to write the adaptive-dispatch metrics "
                         "(convergence steps, committed-vs-best gap; "
                         "'' disables)")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="where to write the serving-session metrics "
                         "(cache-hit rate, compiles, queue latency "
                         "percentiles; '' disables)")
    ap.add_argument("--faults-json", default="BENCH_faults.json",
                    help="where to write the chaos-bench metrics "
                         "(survival rate, degraded-throughput ratio, "
                         "shed rate; '' disables)")
    args = ap.parse_args(argv)
    unknown = [b for b in args.benches if b not in MODULES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from {MODULES}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.quick:
        # Set before bench modules import/run so common.is_quick() and
        # any subprocesses they spawn agree.
        os.environ["REPRO_BENCH_QUICK"] = "1"

    which = args.benches or MODULES
    failures = []
    print("name,us_per_call,derived")
    for name in which:
        t0 = time.time()
        try:
            # import inside the guard: a missing optional dep (e.g.
            # scipy) fails that bench alone, not the whole runner
            mod = importlib.import_module(f"benchmarks.bench_{name}")
            mod.run()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
    from benchmarks.common import metrics
    if args.bench_json:
        payload = {"quick": bool(args.quick), "benches": which,
                   "failures": failures, "metrics": metrics()}
        with open(args.bench_json, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# metrics written to {args.bench_json}", flush=True)
    # The adaptive-dispatch headline (convergence steps, committed-vs-
    # offline-best gap) also lands in its own artifact so CI can gate the
    # serving-path quality independently of the sweep-engine trajectory.
    adaptive = {k: v for k, v in metrics().items()
                if k.startswith("adaptive.")}
    if args.adaptive_json and adaptive:
        with open(args.adaptive_json, "w", encoding="utf-8") as f:
            json.dump({"quick": bool(args.quick), "metrics": adaptive},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# adaptive metrics written to {args.adaptive_json}",
              flush=True)
    # Serving-session headline (executable-cache hit rate, compiles,
    # queue latency): its own artifact so CI can gate the >= 0.5
    # cache-hit floor independently.
    serve = {k: v for k, v in metrics().items()
             if k.startswith("serve.")}
    if args.serve_json and serve:
        with open(args.serve_json, "w", encoding="utf-8") as f:
            json.dump({"quick": bool(args.quick), "metrics": serve},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# serve metrics written to {args.serve_json}",
              flush=True)
    # Chaos-bench headline (survival under injected faults, price of
    # degradation): own artifact so the CI chaos job gates it directly.
    faults = {k: v for k, v in metrics().items()
              if k.startswith("faults.")}
    if args.faults_json and faults:
        with open(args.faults_json, "w", encoding="utf-8") as f:
            json.dump({"quick": bool(args.quick), "metrics": faults},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# faults metrics written to {args.faults_json}",
              flush=True)

    if failures:
        print(f"# {len(failures)} bench(es) failed: "
              + ", ".join(failures), flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
