"""Run one cell of the chip benchmark once and print its result line.

Usage, from the root of a checkout:
    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for. The last line of stdout is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and
(with ``--trace 1``) ``breakdown``, then ``checks``: each number compared
with its limit.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
