#!/usr/bin/env python3
"""One sweep of open-loop rates for a cell, in one process, to find the
highest rate the system sustains without a growing backlog.

    python bench/tools/sweep.py --workload sb8-chat-poisson --seconds 20 --rates 2 4 6 8 --seeds 1

For each rate: requests in the system (sent, not finished) at each quarter
of the window, the drain after it, TTFT's median and 95th percentile over
each half of the window's requests, and tokens/s.  Not part of a benchmark
run."""

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

if __name__ == "__main__":
    import argparse
    import copy

    from benchlib import harness, manifest, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()
    root = HERE.parent
    bench = manifest.load(root)
    mix0 = manifest.traffic(manifest.cell(bench, a.workload)["traffic"], root)
    err = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    for rate, seed in ((r, s) for r in a.rates for s in a.seeds):
        mix = copy.deepcopy(mix0)
        mix["arrivals"]["rate_rps"] = rate
        got = {}
        t0 = time.perf_counter()
        out = harness.run_cell(a.workload, seed, a.seconds, False, root=root,
                               t_start=t0, log=err, override={"traffic": mix},
                               hooks={"window": lambda w: got.update(w=w)})
        w = got["w"]
        T = w.t1 - w.t0
        in_sys = []
        for q in (0.25, 0.5, 0.75, 1.0):
            t = w.t0 + q * T
            in_sys.append(sum(1 for r in w.recs if r.sent <= t and (
                not r.token_times or r.token_times[-1] > t)))
        half = sorted(w.recs, key=lambda r: r.due)
        h1, h2 = half[: len(half) // 2], half[len(half) // 2:]
        tt = lambda rs: stats.percentile([(r.token_times[0] if r.token_times else w.drained_at) - r.due for r in rs], 95)  # noqa: E731
        print(json.dumps({
            "rate": rate, "seed": seed, "correct": out["correct"],
            "compared": out["compared"], "requests": len(w.recs), "failed": stats.failed(w),
            "in_system_quarters": in_sys,
            "drain_s": max([r.token_times[-1] for r in w.recs if r.token_times] + [w.t1]) - w.t1,
            "ttft_p95_first_half": tt(h1), "ttft_p95_second_half": tt(h2),
            "ttft_p50": stats.percentile(stats.ttfts(w), 50),
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        }), flush=True)
