"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload sage-arxiv.full \
        --seed 7 --seconds 10 --trace 0

Run from the root of a checkout on a machine with the chips the cell
asks for. Exits 3, printing no result, when JAX finds no TPU or too few
chips. See ``harness.py`` for how a cell is found and run.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

if __name__ == "__main__":
    import harness
    sys.exit(harness.cli(sys.argv[1:], T_START))
