#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout; no ``PYTHONPATH`` needed.  The last line of
standard output is the result as one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``compared``: each number of the check beside its
limit).  Without a TPU, with fewer chips than the cell asks for, or without
the system under test beside it, it prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.insert(0, str(_HERE.parent / "src"))

if __name__ == "__main__":
    from benchlib.harness import main

    sys.exit(main(t_start=T_START))
