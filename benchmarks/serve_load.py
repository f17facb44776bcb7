"""Production load benchmark: SLO-aware admission + preemption under a
seeded arrival process.

Drives a seeded mixed workload (mostly short interactive requests at
priority 0, a tail of long low-priority batch requests — the serving
regime EC2MoE targets) through the fleet engine on the modeled clock,
twice over the *same* arrival trace:

  * ``priority`` — SLO-class admission ordering + preemption: a running
    low-priority slot is spilled (paged-KV pages gathered out through the
    page tables) at a safe point when an interactive request is blocked,
    and restored later, token stream bit-identical.
  * ``fifo``     — pure submission order, no preemption (the seed's old
    behaviour): a long batch request at the head of the line blocks every
    interactive arrival behind it.

The claim measured: under a burst that oversubscribes the fleet, priority
admission keeps interactive p99 TTFT under the stated target while pure
FIFO — same trace, same fleet, same modeled costs — violates it.  Both
modes must finish every request (``dropped == 0``).  Tokens are computed
for real; stage times use ``timing="modeled"`` so the run is
deterministic: identical seeds reproduce identical arrival traces and
identical percentile metrics.

Report keys per mode/class: ``ttft_p50/p90/p99``, ``tpot_p50/p90/p99``,
``sustained_tok_s``, ``preemptions``, ``dropped``; plus the fleet fault
counters (all zero here — see ``benchmarks.serve_chaos`` for the run
that exercises them).

    PYTHONPATH=src python -m benchmarks.serve_load [--n-requests 1000]
        [--rate-rps R] [--arrival poisson|bursty] [--lanes N]
        [--ttft-target S] [--seed S] [--out bench_serve_load.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict

import jax

from repro.configs import get_config, smoke_config
from repro.models.model import build_model
from repro.serving.common import VirtualClock
from repro.serving.fleet import FleetServingEngine
from repro.serving.loadgen import (
    BATCH,
    INTERACTIVE,
    build_schedule,
    bursty_arrivals,
    drive,
    poisson_arrivals,
    summarize,
)

from benchmarks.fleet_throughput import CLOUD, FLEET_PROFILES
from benchmarks.serve_chaos import FAULT_KEYS
from repro.launch.cache import enable_compile_cache


def _build_engine(model, params, *, n_lanes: int, max_batch: int,
                  admission: str, preemption: bool) -> FleetServingEngine:
    return FleetServingEngine(
        model, params,
        end_profiles=FLEET_PROFILES[:n_lanes],
        cloud_profile=CLOUD,
        cloud_servers=2,
        compression_rank=max(model.cfg.d_model // 4, 1),
        max_batch=max_batch, max_len=160,
        timing="modeled", max_spill=1.0,
        clock=VirtualClock(),
        admission=admission, preemption=preemption,
    )


def run(
    *,
    arch: str = "tinyllama-1.1b",
    num_layers: int = 2,
    n_requests: int = 1000,
    rate_rps: float = 0.0,  # 0 -> the calibrated oversubscription default
    arrival: str = "poisson",
    burst_factor: float = 8.0,
    n_lanes: int = 3,
    max_batch: int = 2,
    ttft_target_s: float = 0.2,
    warmup_frac: float = 0.05,
    seed: int = 0,
    assert_fifo_violates: bool = True,
) -> Dict:
    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    if rate_rps <= 0:
        # Calibrated oversubscription for the 3-lane smoke fleet: total
        # offered load (decode + batch prefill) exceeds the modeled service
        # rate so a FIFO queue grows with the trace, while the interactive
        # share alone fits comfortably — priority admission reaches a
        # steady state and its p99 TTFT stays flat in n.
        rate_rps = 800.0

    if arrival == "poisson":
        arrivals = poisson_arrivals(n_requests, rate_rps, seed)
    elif arrival == "bursty":
        arrivals = bursty_arrivals(
            n_requests, rate_rps, seed, burst_factor=burst_factor
        )
    else:
        raise ValueError(f"arrival={arrival!r}")
    warmup_s = float(arrivals[int(len(arrivals) * warmup_frac)])

    classes = (
        dataclasses.replace(INTERACTIVE, ttft_slo_s=ttft_target_s),
        BATCH,
    )

    modes = {}
    for mode, (admission, preemption) in (
        ("priority", ("priority", True)),
        ("fifo", ("fifo", False)),
    ):
        # Fresh engine AND fresh Request objects per mode: same seed, so
        # both modes replay byte-identical prompts on the same arrivals.
        schedule = build_schedule(arrivals, classes, seed + 1)
        eng = _build_engine(model, params, n_lanes=n_lanes,
                            max_batch=max_batch,
                            admission=admission, preemption=preemption)
        reqs = drive(eng, schedule)
        m = eng.metrics()
        row = {
            "all": summarize(reqs, warmup_s=warmup_s),
            "interactive": summarize(reqs, warmup_s=warmup_s, priority=0),
            "batch": summarize(
                reqs, warmup_s=warmup_s, priority=BATCH.priority
            ),
            "engine_preemptions": m["preemptions"],
            "engine_preempt_restores": m["preempt_restores"],
            "preempt_spill_bytes": m["preempt_spill_bytes"],
            # fault counters (serve_chaos.FAULT_KEYS): all zero on this
            # fault-free harness — their presence keeps the two load
            # benchmarks' report schemas aligned
            **{k: m[k] for k in FAULT_KEYS},
        }
        assert row["all"]["dropped"] == 0, (
            f"{mode}: dropped requests: {row['all']}"
        )
        modes[mode] = row
        inter = row["interactive"]
        print(
            f"[serve_load] {mode:8s} interactive ttft_p99={inter['ttft_p99']:.3f}s "
            f"tpot_p99={inter['tpot_p99']:.4f}s "
            f"tok/s={row['all']['sustained_tok_s']:.1f} "
            f"preempt={m['preemptions']} "
            f"(n={row['all']['n']} finished={row['all']['finished']})",
            flush=True,
        )

    p99_prio = modes["priority"]["interactive"]["ttft_p99"]
    p99_fifo = modes["fifo"]["interactive"]["ttft_p99"]
    assert p99_prio < ttft_target_s, (
        f"priority admission misses the interactive TTFT target: "
        f"p99={p99_prio:.3f}s target={ttft_target_s}s"
    )
    if assert_fifo_violates:
        assert p99_fifo > ttft_target_s, (
            f"FIFO unexpectedly meets the target (load too light to "
            f"discriminate): p99={p99_fifo:.3f}s target={ttft_target_s}s"
        )
    print(
        f"[serve_load] interactive ttft_p99: priority {p99_prio:.3f}s < "
        f"{ttft_target_s}s target < fifo {p99_fifo:.3f}s "
        f"({n_requests} requests, {arrival} arrivals @ {rate_rps:.1f} rps, "
        f"{n_lanes} lanes)",
        flush=True,
    )
    return {
        "arch": cfg.name,
        "n_requests": n_requests,
        "arrival": arrival,
        "rate_rps": rate_rps,
        "burst_factor": burst_factor if arrival == "bursty" else None,
        "n_lanes": n_lanes,
        "max_batch": max_batch,
        "cloud_servers": 2,
        "seed": seed,
        "warmup_s": round(warmup_s, 3),
        "ttft_target_s": ttft_target_s,
        "modes": modes,
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=1000)
    ap.add_argument("--rate-rps", type=float, default=0.0)
    ap.add_argument("--arrival", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--burst-factor", type=float, default=8.0)
    ap.add_argument("--lanes", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--ttft-target", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--no-assert-fifo-violates", action="store_true")
    ap.add_argument("--out", default="bench_serve_load.json")
    args = ap.parse_args()
    row = run(
        num_layers=args.num_layers,
        n_requests=args.n_requests,
        rate_rps=args.rate_rps,
        arrival=args.arrival,
        burst_factor=args.burst_factor,
        n_lanes=args.lanes,
        max_batch=args.max_batch,
        ttft_target_s=args.ttft_target,
        seed=args.seed,
        assert_fifo_violates=not args.no_assert_fifo_violates,
    )
    json.dump([row], open(args.out, "w"), indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
