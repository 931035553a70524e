"""The readings behind the limit of ``correct``, on the chip.

    python benchmarks/chip/control.py --workload phi3-chat-steady \
        --seeds 11,12,13 --seconds 24

For each seed, one run of the cell as ``run.py`` makes it (its own
weights, traffic, warm-up and a window at the cell's load), then the
served tokens' logit gaps against the float32 reference (the program's
readings) and, at the same positions, the gaps of the tokens the int8
reference puts first (the control's readings).  Both are held to the
configuration's limits by the same comparison (``harness/check.py:
judge``): the program has to come out correct and the control not.  All
seeds run in this one process; each prints one JSON line, and the exit
code is 1 unless every program run is correct and every control is not.
The benchmark's runs never run this.
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

from harness import cli, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    devs = cli.devices_or_exit(int(cell["chips"]), allow_cpu=False)
    if devs is None:
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = cli.run_cell(bench, cell, spec.ROOT, devs, seed=seed,
                           seconds=args.seconds, trace=False,
                           out_root=spec.ROOT / ".bench_out", t_start=t0,
                           control=True)
        ctl = res["control"]
        sound &= res["correct"] and not ctl["correct"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control_correct": ctl["correct"],
                          "control_checks": ctl["checks"],
                          "readings": ctl["readings"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                          res["device"]["memory_peak_bytes"]}), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
