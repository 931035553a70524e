"""The knee of a serving cell: one sweep of steady Poisson rates, on the
chip, with the cell's lengths.

    python benchmarks/chip/sweep.py --workload phi3-chat-steady \
        --rates 0.8,1.2,1.6,2.0 --seconds 32 --seed 7

One process builds and warms the cell once (for the lengths of every
rate), then offers each rate for ``--seconds``.  For each it prints one
JSON line: the offered and completed request rates, output tokens/s,
the backlog (requests due and not finished) at the middle and at the end
of the window, and the tails.  The knee is the highest rate whose
backlog does not grow; it is written into the cell's traffic file by
hand, once.  The benchmark's runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from harness import cli, serve, spec, traffic  # noqa: E402


def backlog(win, t: float) -> int:
    """Requests due by ``t`` and not finished by then."""
    n = 0
    for r in win.requests:
        if r.due > t:
            continue
        done = (r.state == "COMPLETED" and r.token_times
                and r.token_times[-1] <= t)
        n += not done
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    devs = cli.devices_or_exit(int(cell["chips"]), allow_cpu=False)
    if devs is None:
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sizes, ref = spec.load_config(cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    rates = [float(r) for r in args.rates.split(",")]
    plans = {}
    for rate in rates:
        steady = dict(mix, knee_rps=rate,
                      phases=[{"seconds": 1.0, "rate_x_knee": 1.0}])
        plans[rate] = traffic.generate(
            steady, vocab_size=int(sizes["vocab_size"]),
            seconds=args.seconds, seed=args.seed)
    counter = serve.CompileCounter()
    eng = serve.ServeCell(sizes, ref, seed=args.seed,
                          out_dir=spec.ROOT / ".bench_out" / "sweep",
                          log=cli.log)
    eng.warm([a for p in plans.values() for a in p], counter)
    cli.log(f"set-up {time.perf_counter() - T_START:.1f} s")
    for rate in rates:
        win = eng.measure(plans[rate], args.seconds, counter)
        m = cli.end_to_end(win, args.seconds, 0.0)
        done = sum(1 for r in win.requests if r.state == "COMPLETED"
                   and r.token_times and r.token_times[-1] <= win.t_end)
        print(json.dumps({
            "rate_rps": rate, "offered": len(win.requests),
            "completed_rps": done / args.seconds,
            "output_tok_s": m["output_tok_s"],
            "backlog_mid": backlog(win, win.t0 + args.seconds / 2),
            "backlog_end": backlog(win, win.t_end),
            "ttft_p90_s": m["ttft_p90_s"], "itl_p95_ms": m.get("itl_p95_ms"),
            "compiles": win.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
