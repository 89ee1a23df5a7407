"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 benchmarks/onchip/run.py --workload vgg16_224.server \
        --seed 7 --seconds 10 --trace 0

Exits non-zero, with no result, where JAX finds fewer TPU chips than
the cell asks for.  See harness/cell.py for the result line.
"""

import time

T_START = time.monotonic()

import sys                                           # noqa: E402
from pathlib import Path                             # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from harness.cell import main                        # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
