"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV — us_per_call is the wall time of
one harness invocation; ``derived`` is the headline metric that maps onto
the paper's claim for that table/figure.

  table1    accuracy gap EC2MoE - EdgeMoE (pp; paper: ~+4.1)
  fig5      EC2MoE saturation throughput multiple vs BrownoutServe (paper 2.2x)
  fig6      EC2MoE latency reduction vs BrownoutServe at the loaded
            operating point (paper -67%)
  fig7      EC2MoE throughput at 10 req/s offered (paper: linear scaling)
  fig8      EC2MoE throughput retention at 40% bandwidth fluctuation
  ablation  -PO-ECC throughput drop (paper -38%)
  roofline  mean roofline fraction over all dry-run cells (single pod)

Full sweeps with JSON outputs: run the individual modules
(``python -m benchmarks.table1_accuracy`` etc.).
"""

from __future__ import annotations

import json
import os
import time

from repro.launch.cache import enable_compile_cache


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def bench_table1():
    from benchmarks.table1_accuracy import run

    rows = run(expert_counts=(8,), datasets=("glue_proxy",), steps=150)
    by = {r["system"]: r["accuracy"] for r in rows}
    return by["ec2moe"] - by["edgemoe"]


def bench_fig5():
    from benchmarks.fig5_6_perf import run, summarize

    rows = run(expert_counts=(16,), n_requests=300)
    return summarize(rows)["throughput_x_vs_brownoutserve"]


def bench_fig6():
    from benchmarks.fig5_6_perf import run, summarize

    rows = run(expert_counts=(16,), n_requests=300)
    return summarize(rows)["latency_reduction_vs_brownoutserve"]


def bench_fig7():
    from benchmarks.fig7_load import run

    rows = run(rates=(10,), n_requests=150)
    return next(r["throughput_rps"] for r in rows if r["system"] == "ec2moe")


def bench_fig8():
    from benchmarks.fig8_bandwidth import run

    rows = run(flucts=(0.0, 0.4), n_requests=150)
    t0 = next(r["throughput_rps"] for r in rows
              if r["system"] == "ec2moe" and r["fluctuation"] == 0.0)
    t4 = next(r["throughput_rps"] for r in rows
              if r["system"] == "ec2moe" and r["fluctuation"] == 0.4)
    return t4 / t0


def bench_ablation():
    from benchmarks.ablation import perf_ablation

    return perf_ablation(n=150)["throughput_drop_no_poecc_pct"]


def bench_roofline():
    from benchmarks.roofline import analyze

    path = os.path.join(os.path.dirname(__file__), "..", "dryrun_results.json")
    if not os.path.exists(path):
        return float("nan")
    rows = analyze(json.load(open(path)))
    ok = [r for r in rows if r.get("status") == "ok" and r["mesh"] == "single"]
    return sum(r["roofline_fraction"] for r in ok) / max(len(ok), 1)


BENCHES = {
    "table1_accuracy_gap_pp": bench_table1,
    "fig5_throughput_x_vs_cloud": bench_fig5,
    "fig6_latency_reduction_vs_cloud": bench_fig6,
    "fig7_throughput_at_10rps": bench_fig7,
    "fig8_tput_retention_at_40pct_fluct": bench_fig8,
    "ablation_no_poecc_tput_drop_pct": bench_ablation,
    "roofline_mean_fraction_single_pod": bench_roofline,
}


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        try:
            val, us = _timed(fn)
            print(f"{name},{us:.0f},{val:.4f}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name},-1,ERROR:{type(e).__name__}:{e}", flush=True)


if __name__ == "__main__":
    main()
