#!/usr/bin/env python3
"""Readings for the limits of a cell's check, in one process: for each seed,
a short window at the cell's own load, then the gaps of the served tokens
(the program) and of the tokens the reference in the control's precision
puts first (the control), both against the float32 reference.

    python bench/tools/calibrate.py --workload <cell> --seconds 10 --seeds 1 2 3 ...
    python bench/tools/calibrate.py ... --int8   # the program's int8 paths on

Prints one JSON line per seed.  Not part of a benchmark run."""

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

if __name__ == "__main__":
    import argparse

    from benchlib import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--int8", action="store_true",
                    help="serve with quantize_kv, quantize_experts and quantize_boundary")
    a = ap.parse_args()
    kw = ({"quantize_kv": True, "quantize_experts": True, "quantize_boundary": True}
          if a.int8 else None)
    err = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    for seed in a.seeds:
        t0 = time.perf_counter()
        out = harness.run_cell(a.workload, seed, a.seconds, False, root=HERE.parent,
                               t_start=t0, log=err, control=True, engine_kw=kw)
        print(json.dumps({"seed": seed, "int8": a.int8, "correct": out["correct"],
                          "attempted": out["attempted"], "failed": out["failed"],
                          **out.get("calibration", {}),
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                          "s": time.perf_counter() - t0}), flush=True)
