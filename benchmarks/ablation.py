"""Ablation study: remove (1) HL-GGN and (2) PO-ECC (paper §Ablation).

Paper's findings to reproduce qualitatively:
  - HL-GGN   : accuracy -2.1%, latency +23%
  - PO-ECC   : throughput -38%, latency +45%
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List

import numpy as np

from repro.configs.switch_base import with_experts
from repro.data.pipeline import DataConfig
from repro.sim.policies import PolicyConfig, ec2moe_stages, make_requests
from repro.sim.simulator import Link, SimRequest, Stage, poisson_arrivals, simulate

from benchmarks.common import eval_tiny, tiny_switch, train_tiny
from repro.launch.cache import enable_compile_cache


def accuracy_ablation(E: int = 16, steps: int = 300, seed: int = 0) -> Dict:
    """EC2MoE vs EC2MoE-without-HL-GGN (flat gate, no hardware-aware
    selection -> compression noise hits an unstructured router)."""
    out = {}
    dcfg = DataConfig(task="glue_proxy", vocab_size=512, seq_len=64,
                      n_latent_tasks=16, seed=seed)
    full_cfg = tiny_switch(E, "ec2moe")
    m1, s1 = train_tiny(full_cfg, dcfg, steps=steps, seed=seed)
    out["ec2moe"] = eval_tiny(m1, s1["params"], dcfg) * 100
    flat_cfg = full_cfg.replace(
        moe=dataclasses.replace(full_cfg.moe, num_groups=1)
    )
    m2, s2 = train_tiny(flat_cfg, dcfg, steps=steps, seed=seed)
    out["no_hlggn"] = eval_tiny(m2, s2["params"], dcfg) * 100
    out["acc_delta_pct"] = out["no_hlggn"] - out["ec2moe"]
    return out


def perf_ablation(E: int = 16, op_rate: float = 8.0, sat_rate: float = 60.0,
                  n: int = 240, seed: int = 0):
    """Throughput measured at saturation; latency at the loaded operating
    point (EC2MoE base uses its load-aware plan, as in fig. 5/6)."""
    cfg = with_experts(E)
    pc = PolicyConfig()
    arr_sat = poisson_arrivals(sat_rate, n, seed)
    arr_op = poisson_arrivals(op_rate, n, seed + 1)

    def run(reqs):
        return simulate(reqs, link=Link(0.3, seed=seed),
                        end_servers=pc.n_end_devices,
                        cloud_servers=pc.n_cloud_gpus)

    def reqs_from(proto, arrivals):
        return [
            SimRequest(i, float(t),
                       [Stage(s.resource, s.service_s, s.payload_bytes, s.jitter)
                        for s in proto])
            for i, t in enumerate(arrivals)
        ]

    base_sat = run(make_requests("ec2moe", cfg, pc, arr_sat, offered_rps=0))
    base_op = run(make_requests("ec2moe", cfg, pc, arr_op, offered_rps=op_rate))

    # -HL-GGN: without hardware-aware selection the end tier cannot host
    # experts, so MoE layers (and the lost gate saving) move to the cloud;
    # the end keeps only the dense front (~25% of its planned compute).
    def no_hlggn_proto(proto):
        out, moved = [], 0.0
        end_rate = pc.end_profile.peak_gflops * pc.end_efficiency
        cloud_rate = pc.cloud_profile.peak_gflops * pc.cloud_efficiency
        for s in proto:
            if s.resource == "end":
                out.append(Stage("end", s.service_s * 0.25))
                moved += s.service_s * 0.75
            elif s.resource == "cloud":
                out.append(Stage("cloud",
                                 s.service_s + moved * end_rate / cloud_rate,
                                 jitter=s.jitter))
            else:
                out.append(s)
        return out

    nh_sat = run(reqs_from(no_hlggn_proto(
        ec2moe_stages(cfg, pc, offered_rps=0)), arr_sat))
    nh_op = run(reqs_from(no_hlggn_proto(
        ec2moe_stages(cfg, pc, offered_rps=op_rate)), arr_op))

    # -PO-ECC: no compression, no pipelining: the request executes serially
    # while HOLDING the end device (no cross-request overlap), raw boundary.
    proto = ec2moe_stages(cfg, dataclasses.replace(pc, compression_rank=0),
                          offered_rps=0)
    link = Link(0.3, seed=seed)
    total = sum(
        (link.rtt_s / 2 + s.payload_bytes * 8 / 0.3e9)
        if s.resource == "link" else s.service_s
        for s in proto
    )
    np_sat = run([SimRequest(i, float(t), [Stage("end", total)])
                  for i, t in enumerate(arr_sat)])
    np_op = run([SimRequest(i, float(t), [Stage("end", total)])
                 for i, t in enumerate(arr_op)])

    return {
        "latency_increase_no_hlggn_pct": 100 * (
            nh_op["latency_mean_s"] / base_op["latency_mean_s"] - 1
        ),
        "throughput_drop_no_hlggn_pct": 100 * (
            1 - nh_sat["throughput_rps"] / base_sat["throughput_rps"]
        ),
        "throughput_drop_no_poecc_pct": 100 * (
            1 - np_sat["throughput_rps"] / base_sat["throughput_rps"]
        ),
        "latency_increase_no_poecc_pct": 100 * (
            np_op["latency_mean_s"] / base_op["latency_mean_s"] - 1
        ),
        "base_sat_rps": base_sat["throughput_rps"],
        "base_op_latency_s": base_op["latency_mean_s"],
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--out", default="bench_ablation.json")
    args = ap.parse_args()
    acc = accuracy_ablation(steps=args.steps)
    perf = perf_ablation()
    print("[ablation] accuracy:", {k: round(v, 2) for k, v in acc.items()})
    print("[ablation] -HL-GGN latency:",
          f"+{perf['latency_increase_no_hlggn_pct']:.0f}% (paper: +23%)")
    print("[ablation] -PO-ECC throughput:",
          f"-{perf['throughput_drop_no_poecc_pct']:.0f}% (paper: -38%), "
          f"latency +{perf['latency_increase_no_poecc_pct']:.0f}% (paper: +45%)")
    json.dump({"accuracy": acc, "perf": {k: v for k, v in perf.items()
                                          if not isinstance(v, dict)}},
              open(args.out, "w"), indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
