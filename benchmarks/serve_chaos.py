"""Chaos replay benchmark: the serve_load trace under an injected fault
schedule, with recovery invariants asserted.

Replays one seeded arrival trace through the fleet engine twice on the
modeled clock:

  * ``clean`` — no faults: the PR 6 load-harness baseline.
  * ``chaos`` — the same trace with a deterministic fault schedule fired
    against it: one lane crash (later recovered), one link blackout
    window on a surviving lane (driving that lane's cloud-only replan),
    and a burst of flaky boundary transfers (retried under bounded
    backoff).

Asserted invariants (the PR's acceptance bar):

  * zero lost and zero duplicated requests under chaos — lane death
    migrates in-flight decode via the spill/restore path, it never drops;
  * greedy tokens bit-identical chaos-vs-clean for EVERY request (the
    boundary runs uncompressed here, so migration, split-0 degradation
    and retries are pure *scheduling* perturbations);
  * bounded interactive p99 TTFT inflation: ``p99_chaos <= p99_clean +
    fault_window_s + slack`` where ``fault_window_s`` is the total
    injected unavailability (crash window + blackout window) — a faulted
    request can be delayed by a window, but recovery must not let delays
    compound past it;
  * per-seed determinism: a repeat chaos run reproduces tokens, fire log
    and summaries bit-for-bit;
  * compressed-boundary chaos (``compression_rank > 0``): faults that
    change WHERE a request computes (migration off a dead lane, a
    placement shifted downstream of one, split-0 degradation on the
    blacked-out lane) legitimately change its tokens, because the codec
    truncates the boundary at the planned split.  The affected set is
    derived from the fire log plus the placement log, and every request
    OUTSIDE it must stay bit-identical chaos-vs-clean.

Report: ``BENCH_serve_chaos.json`` with both runs' per-class summaries,
the fleet fault counters (``lane_failures``, ``migrations``,
``migration_spill_bytes``, ``transfer_retries``, ``degraded_ticks``,
``link_blackout_s``), and the fired schedule.

    PYTHONPATH=src python -m benchmarks.serve_chaos [--n-requests 600]
        [--lanes 3] [--seed 0] [--out BENCH_serve_chaos.json]
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
from repro.serving.faults import ChaosInjector, FaultEvent, FaultSchedule
from repro.serving.fleet import FleetServingEngine
from repro.serving.loadgen import (
    BATCH,
    INTERACTIVE,
    build_schedule,
    drive,
    poisson_arrivals,
    summarize,
)

from benchmarks.fleet_throughput import CLOUD, FLEET_PROFILES
from repro.launch.cache import enable_compile_cache

# Fault counters every chaos report carries (and serve_load reports as
# all-zero on its fault-free runs).
FAULT_KEYS = (
    "lane_failures", "lane_recoveries", "migrations", "migration_restores",
    "migration_spill_bytes", "transfer_retries", "degraded_ticks",
    "link_blackout_s", "cloud_server_failures",
)


def _build_engine(model, params, *, n_lanes: int, max_batch: int,
                  compression_rank: int = 0) -> FleetServingEngine:
    # compression_rank=0 (the default): the boundary is exact, so
    # chaos-vs-clean token parity is total — any divergence is a recovery
    # bug, not codec noise.  The compressed-parity phase re-runs with
    # rank>0, where parity is asserted on the fault-unaffected set only.
    return FleetServingEngine(
        model, params,
        end_profiles=FLEET_PROFILES[:n_lanes],
        cloud_profile=CLOUD,
        cloud_servers=2,
        compression_rank=compression_rank,
        max_batch=max_batch, max_len=160,
        timing="modeled", max_spill=1.0,
        clock=VirtualClock(),
    )


def _fault_schedule(horizon_s: float, n_lanes: int) -> FaultSchedule:
    """The benchmark's declared chaos: timed against the trace horizon so
    the faults land while the fleet is under load at any request count."""
    # crash a mid-fleet lane placement actually loads (the last lane is
    # the straggler profile and often sits idle under max_spill), black
    # out the strongest lane's link — both faults must hit live traffic
    crash_lane = 1 if n_lanes > 1 else 0
    blackout_lane = 0
    nominal = FLEET_PROFILES[blackout_lane].net_gbps
    return FaultSchedule([
        FaultEvent(0.10 * horizon_s, "transfer_flaky", device=0, count=3),
        FaultEvent(0.20 * horizon_s, "lane_crash", device=crash_lane),
        FaultEvent(0.45 * horizon_s, "lane_recover", device=crash_lane),
        FaultEvent(0.55 * horizon_s, "link_blackout", device=blackout_lane),
        FaultEvent(0.75 * horizon_s, "link_recover", device=blackout_lane,
                   gbps=nominal),
    ])


def _one_run(model, params, arrivals, classes, seed, *, n_lanes, max_batch,
             chaos: bool, compression_rank: int = 0):
    schedule = build_schedule(arrivals, classes, seed + 1)
    eng = _build_engine(model, params, n_lanes=n_lanes, max_batch=max_batch,
                        compression_rank=compression_rank)
    injector = None
    if chaos:
        horizon = float(arrivals[-1])
        injector = ChaosInjector(
            _fault_schedule(horizon, n_lanes), eng
        )
    reqs = drive(eng, schedule)
    return eng, reqs, injector


def run(
    *,
    arch: str = "tinyllama-1.1b",
    num_layers: int = 2,
    n_requests: int = 600,
    rate_rps: float = 800.0,
    n_lanes: int = 3,
    max_batch: int = 2,
    warmup_frac: float = 0.05,
    seed: int = 0,
    p99_slack_s: float = 0.05,
) -> Dict:
    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    arrivals = poisson_arrivals(n_requests, rate_rps, seed)
    warmup_s = float(arrivals[int(len(arrivals) * warmup_frac)])
    horizon = float(arrivals[-1])
    classes = (dataclasses.replace(INTERACTIVE, ttft_slo_s=0.2), BATCH)

    runs: Dict[str, Dict] = {}
    tokens: Dict[str, Dict[int, list]] = {}
    fire_logs: Dict[str, list] = {}
    for name, chaos in (("clean", False), ("chaos", True), ("chaos2", True)):
        eng, reqs, injector = _one_run(
            model, params, arrivals, classes, seed,
            n_lanes=n_lanes, max_batch=max_batch, chaos=chaos,
        )
        m = eng.metrics()
        row = {
            "all": summarize(reqs, warmup_s=warmup_s),
            "interactive": summarize(reqs, warmup_s=warmup_s, priority=0),
            "batch": summarize(reqs, warmup_s=warmup_s,
                               priority=BATCH.priority),
            **{k: m[k] for k in FAULT_KEYS},
        }
        # exactly-once: nothing dropped, nothing finished twice
        assert row["all"]["dropped"] == 0, f"{name}: lost requests: {row}"
        ids = [r.request_id for r in eng.finished]
        assert len(ids) == len(set(ids)) == n_requests, (
            f"{name}: {len(ids)} finishes over {len(set(ids))} unique ids"
        )
        tokens[name] = {r.request_id: list(r.generated) for r in reqs}
        if injector is not None:
            assert injector.pending == 0, "declared faults never fired"
            fire_logs[name] = injector.fire_log()
            row["fired"] = fire_logs[name]
        runs[name] = row
        print(
            f"[serve_chaos] {name:6s} interactive "
            f"ttft_p99={row['interactive']['ttft_p99']:.3f}s "
            f"migrations={row['migrations']} "
            f"retries={row['transfer_retries']} "
            f"blackout={row['link_blackout_s']:.2f}s "
            f"(finished={row['all']['finished']}/{n_requests})",
            flush=True,
        )

    # greedy-token parity: chaos only moves WHEN tokens happen, never which
    diverged = [
        rid for rid in tokens["clean"]
        if tokens["clean"][rid] != tokens["chaos"][rid]
    ]
    assert not diverged, f"tokens diverged under chaos: requests {diverged}"

    # per-seed determinism: repeat chaos run is bit-identical
    assert tokens["chaos"] == tokens["chaos2"], "chaos rerun tokens differ"
    assert fire_logs["chaos"] == fire_logs["chaos2"], "fire logs differ"
    assert runs["chaos"] == runs["chaos2"], "chaos rerun summaries differ"

    # bounded p99 inflation: the documented bound is the total *measured*
    # unavailability (crash outage from the fire log — events land at the
    # first tick past their time on a coarse modeled clock, so the
    # declared window underestimates — plus the metered blackout seconds)
    # plus a fixed slack for retry backoff and replan latency.  Recovery
    # may cost a faulted request one outage window; it must never let
    # delays compound past it.
    fired = {(d["kind"], d["device"]): d["t_fired_s"]
             for d in fire_logs["chaos"]}
    crash_lane = 1 if n_lanes > 1 else 0
    crash_outage_s = (
        fired[("lane_recover", crash_lane)] - fired[("lane_crash", crash_lane)]
    )
    fault_window_s = crash_outage_s + runs["chaos"]["link_blackout_s"]
    p99_clean = runs["clean"]["interactive"]["ttft_p99"]
    p99_chaos = runs["chaos"]["interactive"]["ttft_p99"]
    bound = p99_clean + fault_window_s + p99_slack_s
    assert p99_chaos <= bound, (
        f"interactive p99 TTFT inflation unbounded: chaos {p99_chaos:.3f}s "
        f"> clean {p99_clean:.3f}s + window {fault_window_s:.3f}s "
        f"+ slack {p99_slack_s}s"
    )
    assert runs["chaos"]["lane_failures"] == 1
    assert runs["chaos"]["migration_restores"] == runs["chaos"]["migrations"]
    print(
        f"[serve_chaos] p99 bound holds: chaos {p99_chaos:.3f}s <= "
        f"clean {p99_clean:.3f}s + fault window {fault_window_s:.3f}s "
        f"+ slack {p99_slack_s}s; parity exact over {n_requests} requests",
        flush=True,
    )
    runs["chaos2"] = "identical to chaos (asserted)"  # keep the JSON small

    # ---- compressed-boundary chaos: parity on the fault-unaffected set.
    # With rank > 0 the codec truncates the boundary activation at the
    # *planned split*, so a fault that moves a request to a different
    # lane (migration, or a placement shifted downstream of one) or
    # changes its lane's split (the blacked-out lane degrades to split 0)
    # legitimately changes its tokens.  The affected set is exactly those
    # requests, read off the fire log + placement log; everything outside
    # it took the same codec path and must stay bit-identical.
    rank = max(cfg.d_model // 4, 1)
    comp_tokens: Dict[str, Dict[int, list]] = {}
    comp_placed: Dict[str, Dict[int, list]] = {}
    comp_fired: list = []
    for name, chaos in (("clean", False), ("chaos", True)):
        eng, reqs, injector = _one_run(
            model, params, arrivals, classes, seed,
            n_lanes=n_lanes, max_batch=max_batch, chaos=chaos,
            compression_rank=rank,
        )
        ids = [r.request_id for r in eng.finished]
        assert len(ids) == len(set(ids)) == n_requests, (
            f"compressed {name}: exactly-once violated"
        )
        comp_tokens[name] = {r.request_id: list(r.generated) for r in reqs}
        comp_placed[name] = {}
        for p in eng.placed:
            comp_placed[name].setdefault(p["request_id"], []).append(
                p["device"]
            )
        if injector is not None:
            assert injector.pending == 0, "declared faults never fired"
            comp_fired = injector.fire_log()
    # lanes whose split changed under chaos: the whole blackout window is
    # a degradation hazard, so the lane is excluded wholesale
    degraded_lanes = {
        d["device"] for d in comp_fired if d["kind"] == "link_blackout"
    }
    affected = {
        rid for rid in comp_tokens["clean"]
        # placed differently than the clean run (fault-shifted placement)
        if comp_placed["clean"].get(rid) != comp_placed["chaos"].get(rid)
        # migrated off a dead lane (restored at the destination's split)
        or len(comp_placed["chaos"].get(rid, [])) > 1
        # ran on a lane that degraded its split during the blackout
        or degraded_lanes & set(comp_placed["chaos"].get(rid, []))
    }
    unaffected = sorted(set(comp_tokens["clean"]) - affected)
    assert unaffected, (
        "chaos touched every request: compressed parity set is empty"
    )
    comp_diverged = [
        rid for rid in unaffected
        if comp_tokens["clean"][rid] != comp_tokens["chaos"][rid]
    ]
    assert not comp_diverged, (
        f"rank-{rank} tokens diverged for fault-UNAFFECTED requests "
        f"{comp_diverged[:8]} (of {len(comp_diverged)})"
    )
    print(
        f"[serve_chaos] compressed (rank={rank}): "
        f"{len(affected)} affected / {len(unaffected)} unaffected — "
        f"unaffected parity exact",
        flush=True,
    )

    return {
        "arch": cfg.name,
        "n_requests": n_requests,
        "rate_rps": rate_rps,
        "n_lanes": n_lanes,
        "max_batch": max_batch,
        "cloud_servers": 2,
        "seed": seed,
        "warmup_s": round(warmup_s, 3),
        "horizon_s": round(horizon, 3),
        "fault_window_s": round(fault_window_s, 3),
        "p99_slack_s": p99_slack_s,
        "p99_bound_s": round(bound, 4),
        "token_parity": "exact",
        "compressed": {
            "compression_rank": rank,
            "affected": len(affected),
            "unaffected": len(unaffected),
            "token_parity": "exact on unaffected set",
        },
        "runs": runs,
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=600)
    ap.add_argument("--rate-rps", type=float, default=800.0)
    ap.add_argument("--lanes", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--p99-slack", type=float, default=0.05)
    ap.add_argument("--out", default="BENCH_serve_chaos.json")
    args = ap.parse_args()
    report = run(
        num_layers=args.num_layers,
        n_requests=args.n_requests,
        rate_rps=args.rate_rps,
        n_lanes=args.lanes,
        max_batch=args.max_batch,
        seed=args.seed,
        p99_slack_s=args.p99_slack,
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[serve_chaos] wrote {args.out}")


if __name__ == "__main__":
    main()
