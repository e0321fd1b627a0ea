#!/usr/bin/env python3
"""Snow chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for; ``BENCHMARK.json`` names the cells.  See
``snowbench/harness.py`` for what a run does and prints.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from snowbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
