"""Table 1: accuracy of EC2MoE vs BrownoutServe vs EdgeMoE, across expert
counts, on the two proxy datasets (GLUE/SQuAD stand-ins; see
repro.data.pipeline for the task definitions and EXPERIMENTS.md for the
proxy rationale — this container is offline).

Per cell: train a smoke-scale Switch-Base variant (paper setting: top-1,
seq 256 -> scaled to 64, batch 4 -> 16) under each system's constraints and
evaluate under its serving conditions:
  ec2moe        — group gate + jointly-trained dispatch compression
  brownoutserve — flat gate, full experts, eval with p_net expert loss
  edgemoe       — flat gate, static 40% expert subset (train + eval)
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, List

from repro.data.pipeline import DataConfig

from benchmarks.common import (
    SYSTEMS,
    eval_tiny,
    static_mask,
    tiny_switch,
    train_tiny,
)
from repro.launch.cache import enable_compile_cache


def run(
    expert_counts=(8, 16, 32, 64),
    datasets=("glue_proxy", "squad_proxy"),
    steps: int = 300,
    p_net: float = 0.01,
    seed: int = 0,
) -> List[Dict]:
    rows = []
    for ds in datasets:
        for E in expert_counts:
            dcfg = DataConfig(task=ds, vocab_size=512, seq_len=64,
                              n_latent_tasks=16, seed=seed)
            for system in SYSTEMS:
                cfg = tiny_switch(E, system)
                train_mask = (
                    static_mask(E, cfg.moe.local_selection_cap)
                    if system == "edgemoe"
                    else None
                )
                model, st = train_tiny(
                    cfg, dcfg, steps=steps, train_mask=train_mask, seed=seed
                )
                acc = eval_tiny(
                    model,
                    st["params"],
                    dcfg,
                    expert_mask=train_mask,
                    drop_p=(p_net if system == "brownoutserve" else 0.0),
                )
                rows.append(
                    dict(dataset=ds, experts=E, system=system,
                         accuracy=round(acc * 100, 2), steps=steps)
                )
                print(f"[table1] {ds} E={E} {system}: acc={acc*100:.2f}%",
                      flush=True)
    return rows


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", default="8,16,32,64")
    ap.add_argument("--datasets", default="glue_proxy,squad_proxy")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--out", default="bench_table1.json")
    args = ap.parse_args()
    rows = run(
        tuple(int(e) for e in args.experts.split(",")),
        tuple(args.datasets.split(",")),
        steps=args.steps,
    )
    json.dump(rows, open(args.out, "w"), indent=1)
    # paper-style summary: per-system mean accuracy
    for ds in set(r["dataset"] for r in rows):
        line = {s: [] for s in SYSTEMS}
        for r in rows:
            if r["dataset"] == ds:
                line[r["system"]].append(r["accuracy"])
        means = {s: sum(v) / len(v) for s, v in line.items() if v}
        print(f"[table1] {ds} means:", means)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
