"""``run.py``: one process per run: load, warm, measure, check, print.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared beside its limit.  Standard error ends with the
same checks, one per line.  With no TPU, or fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

from harness import spec as specmod
from harness.stats import censored, gaps, percentile


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _args(argv):
    ap = argparse.ArgumentParser(description="one run of a benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_exit(chips: int, allow_cpu: bool):
    """The chips the cell asks for; None (the caller exits 2) without a
    TPU or with fewer chips."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"run.py: no accelerator: {e}")
        return None
    if devs[0].platform != "tpu" and not allow_cpu:
        log(f"run.py: no TPU (JAX platform {devs[0].platform!r}); the "
            f"benchmark runs only on the chip")
        return None
    if len(devs) < chips:
        log(f"run.py: the cell needs {chips} chips, JAX sees {len(devs)}")
        return None
    return devs[:chips]


def end_to_end(win, seconds: float, setup_s: float) -> Dict[str, float]:
    """The serving end-to-end metrics of one window (every request due
    in it; a first token not there by the end counts at its age then)."""
    reqs = win.requests
    ttft = censored([r.due for r in reqs],
                    [r.token_times[0] if r.token_times else None
                     for r in reqs], win.t_end)
    itl: List[float] = []
    out = 0
    for r in reqs:
        itl += gaps(r.token_times, win.t_end)
        out += sum(1 for t in r.token_times if t <= win.t_end)
    m = {"setup_s": setup_s, "ttft_p90_s": percentile(ttft, 90),
         "output_tok_s": out / seconds}
    if itl:
        m["itl_mean_ms"] = sum(itl) / len(itl) * 1e3
        m["itl_p95_ms"] = percentile(itl, 95) * 1e3
    return m


def describe_window(win, spans) -> str:
    """One line on the window's latencies and its longest stall (the
    longest wait, with work handed to the engine, for its next decode
    step), with the engine spans inside that stall: what the next reader
    of a far-off run needs first."""
    itl: List[float] = []
    for r in win.requests:
        itl += gaps(r.token_times, win.t_end)
    ttft = censored([r.due for r in win.requests],
                    [r.token_times[0] if r.token_times else None
                     for r in win.requests], win.t_end)
    ms = [f"{percentile(itl, q) * 1e3:.1f}" if itl else "-"
          for q in (50, 90, 95, 99)]
    stalls = [(a[1], b[1]) for a, b in zip(win.marks, win.marks[1:])
              if b[0] == "step" and b[1] <= win.t_end]
    lo, hi = max(stalls, key=lambda p: p[1] - p[0], default=(win.t0, win.t0))
    inside: Dict[str, List[float]] = {}
    for s in spans:
        if s["start"] >= lo and s["end"] <= hi:
            inside.setdefault(s["name"], []).append(s["end"] - s["start"])
    what = ", ".join(f"{n} x{len(d)} {sum(d):.3f} s"
                     for n, d in sorted(inside.items(),
                                        key=lambda kv: -sum(kv[1])))
    return (f"window tails: itl mean "
            f"{(sum(itl) / len(itl) * 1e3) if itl else 0:.1f} ms, "
            f"p50/p90/p95/p99 {'/'.join(ms)} ms over {len(itl)} gaps; "
            f"ttft p50/p90 {percentile(ttft, 50) or 0:.3f}/"
            f"{percentile(ttft, 90) or 0:.3f} s; longest stall before a "
            f"step {hi - lo:.3f} s at {lo - win.t0:.1f} s "
            f"({what or 'no engine span inside'})")


def run(argv=None, *, t_start: float, allow_cpu: bool = False,
        bench_root: pathlib.Path = specmod.ROOT,
        out_root: Optional[pathlib.Path] = None) -> int:
    """One run of a cell; returns the exit code."""
    args = _args(argv)
    bench = specmod.load_benchmark(bench_root)
    cell = specmod.workload(bench, args.workload)
    try:
        import jax  # noqa: F401
        import repro  # noqa: F401
    except ImportError as e:
        log(f"run.py: the system under test is not importable: {e}")
        return 2
    devs = devices_or_exit(int(cell["chips"]), allow_cpu)
    if devs is None:
        return 2
    out_root = out_root or (bench_root / ".bench_out")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_root / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = run_cell(bench, cell, bench_root, devs, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      out_root=out_root, t_start=t_start)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, cell, root: pathlib.Path, devs, *, seed: int,
             seconds: float, trace: bool, out_root: pathlib.Path,
             t_start: float, control: bool = False) -> Dict[str, Any]:
    """Build, warm, measure and check one run of a serving cell; the
    result line as a dict (with ``control`` also the int8 control's
    readings and its own ``correct`` and ``checks``, under
    ``control``)."""
    import jax
    from harness import check, serve, traffic
    from harness.peaks import peaks_for
    dev = devs[0]
    out_dir = out_root / cell["name"]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    base = root / "benchmarks" / "chip"
    sizes, ref = specmod.load_config(cell["config"], base)
    mix = specmod.load_traffic(cell["traffic"], base)
    if sizes.get("task") != "serve":
        raise ValueError(f"task {sizes.get('task')!r} has no runner")
    counter = serve.CompileCounter()
    arrivals = traffic.generate(mix, vocab_size=int(sizes["vocab_size"]),
                                seconds=seconds, seed=seed)
    log(f"{cell['name']}: {len(arrivals)} requests due in {seconds:g} s "
        f"on {dev.device_kind} x{len(devs)}")
    eng = serve.ServeCell(sizes, ref, seed=seed, out_dir=out_dir, log=log)
    eng.warm(arrivals, counter)
    annotate = None
    if trace:
        from harness import trace as tracemod
        trace_dir = out_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracemod.start(trace_dir)
        annotate = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t_start
    win = eng.measure(arrivals, seconds, counter, annotate=annotate)
    if trace:
        tracemod.stop()
    stats = dev.memory_stats() or {}
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))
    log(f"set-up {setup_s:.1f} s; window: "
        f"{sum(1 for r in win.requests if r.state == 'COMPLETED')} "
        f"completed, {win.decode_steps} decode steps, {win.compiles} "
        f"compiles inside, generator at most {win.late_s * 1e3:.1f} ms late; "
        f"peak {peak / 2**30:.2f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f}")

    spans = eng.engine_spans()
    log(describe_window(win, spans))
    eng.free_engine()
    # correctness: the served tokens against the plain reference
    done = [(r.prompt, r.tokens) for r in win.requests
            if r.state == "COMPLETED" and r.tokens is not None
            and len(r.tokens)]
    lim = sizes["limits"]
    picked = check.pick_sample(done, seed, int(lim["min_tokens"]),
                               int(lim["max_requests"]))
    pad_to = int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
    pad_to = -(-pad_to // 128) * 128
    t_ref = time.perf_counter()
    res = check.served_gaps(ref, sizes, eng.params,
                            [done[i] for i in picked], pad_to,
                            control=control)
    log(f"reference: {res['requests_checked']} requests, "
        f"{res['tokens_checked']} served tokens in "
        f"{time.perf_counter() - t_ref:.1f} s")
    failed = sum(1 for r in win.requests
                 if r.state not in (None, "COMPLETED", "CANCELLED"))
    checks, correct = check.judge(res, lim, failed)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": len(win.requests),
                              "failed": failed}
    wanted = {m["name"]: m for m in (specmod.per_layer_for(bench, cell["name"])
                                     if trace else
                                     specmod.end_to_end_for(bench, cell["name"]))}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        tr = tracemod.load(trace_dir, [(s["name"], s["start"], s["end"])
                                       for s in spans], win.t0)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
        ctx = {"window": win, "trace": tr, "peaks": peaks, "sizes": sizes,
               "matmul_params": ref.matmul_params(sizes),
               "engine": sizes["engine"], "seconds": seconds}
        for name in wanted:
            value = specmod.metric_reader(name, base).read(ctx)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": wanted[name]["unit"]}
    else:
        for name, value in end_to_end(win, seconds, setup_s).items():
            if name in wanted and value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": wanted[name]["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        # The control in the program's place, held to the same limits.
        c_checks, c_correct = check.judge(check.control_gaps(res), lim,
                                          failed)
        result["control"] = {"correct": c_correct, "checks": c_checks,
                             "readings": dict(res)}
    result["checks"] = checks
    del eng
    return result


def main(argv=None, *, t_start: float) -> int:
    try:
        return run(argv, t_start=t_start)
    except Exception:
        traceback.print_exc()
        return 1
