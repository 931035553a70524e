"""One run of a benchmark cell on the chip (see ``harness/cli.py``).

    python benchmarks/chip/run.py --workload phi3-chat-steady \
        --seed 1234 --seconds 48 --trace 0
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
