"""Chip benchmark of the serving stack: one run of one BENCHMARK.json cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, which must hold the TPU chips the
cell asks for; anywhere else it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0, root=ROOT))
