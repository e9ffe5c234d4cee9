"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is one JSON object; the
numbers compared to decide ``correct`` are the last lines of standard
error.  See PERF.md for the cells and metrics.
"""
import time

START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from harness.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(start=START))
