"""End-to-end driver: train a small group-gated MoE on the latent-task
mixture, then SERVE it through the full EC2MoE stack —

  1. batched continuous-batching engine (repro.serving.engine),
  2. the end-cloud collaborative pipeline (PO-ECC): route-aware layer split
     (eq. 9-11), hardware-aware expert masks on the end tier (eq. 2-4), and
     low-rank boundary compression (eq. 8), and
  3. the streaming end-cloud decode engine (repro.serving.stream): token-level
     two-tier pipeline with a double-buffered boundary and dynamic replanning
     when the link bandwidth drifts.

    PYTHONPATH=src python examples/serve_endcloud.py [--steps 200]
"""

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import tiny_switch, train_tiny
from repro.core.hardware import PROFILES, DeviceState
from repro.data.pipeline import DataConfig, batches, eval_accuracy
from repro.serving.endcloud import EndCloudPipeline
from repro.serving.engine import Request, ServingEngine
from repro.serving.stream import EndCloudServingEngine
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    # 1) train
    cfg = tiny_switch(8, "ec2moe")
    dcfg = DataConfig(task="lm", vocab_size=512, seq_len=64, n_latent_tasks=4)
    print(f"training {cfg.name} (E={cfg.moe.num_experts}, K={cfg.moe.num_groups}) "
          f"for {args.steps} steps ...")
    model, st = train_tiny(cfg, dcfg, steps=args.steps, seed=0)
    params = st["params"]
    print("final train metrics:", st["metrics"])

    # 2) batched serving engine
    eng = ServingEngine(model, params, max_batch=4, max_len=96)
    rng = np.random.default_rng(0)
    for i in range(8):
        eng.submit(Request(i, rng.integers(0, 500, 24).astype(np.int32),
                           max_new_tokens=8))
    done = eng.run()
    lat = [r.finish_time - r.submit_time for r in done]
    print(f"engine: {len(done)} requests served, "
          f"mean wall latency {np.mean(lat)*1e3:.0f} ms, "
          f"sample output {done[0].generated}")

    # 3) end-cloud pipeline (Xeon end + A100 cloud, paper testbed)
    pipe = EndCloudPipeline(
        model, params,
        end_profile=PROFILES["xeon-4214r"],
        cloud_profile=PROFILES["a100"],
        end_state=DeviceState(cpu_free=0.8, mem_free=0.6),
        compression_rank=cfg.d_model // 2,
    )
    print(f"route-aware plan: split at block {pipe.split}/{cfg.block_repeat}, "
          f"compress={pipe.plan.compress_boundary}, "
          f"end expert mask={None if pipe.end_mask is None else int(pipe.end_mask.sum())} experts")
    b = next(iter(batches(dcfg, 8, 1, seed=3)))
    logits, m = pipe.run_batch(jnp.asarray(b["tokens"]))
    print(f"pipeline metrics: {m}")
    print(f"pipeline accuracy on held-out batch: "
          f"{eval_accuracy(np.asarray(logits), b['labels'])*100:.1f}%")

    # 4) streaming end-cloud decode: continuous batching across the two
    #    tiers, boundary double-buffered, replanned when the link drifts
    seng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["xeon-4214r"],
        cloud_profile=PROFILES["a100"],
        end_state=DeviceState(cpu_free=0.8, mem_free=0.6),
        compression_rank=cfg.d_model // 2,
        max_batch=4, max_len=96,
    )
    for i in range(8):
        seng.submit(Request(100 + i, rng.integers(0, 500, 24).astype(np.int32),
                            max_new_tokens=8))
    for _ in range(4):
        seng.step()
    seng.observe_bandwidth(0.03)  # link degrades to 30 Mbps mid-stream
    done = seng.run()
    sm = seng.metrics()
    print(f"streaming engine: {len(done)} requests, split={sm['split']}, "
          f"pipelined step {sm['pipelined_step_s']*1e3:.2f} ms vs serial "
          f"{sm['serial_step_s']*1e3:.2f} ms, boundary bytes {sm['bytes_up']}, "
          f"replans={sm['replan_events']}")


if __name__ == "__main__":
    main()
