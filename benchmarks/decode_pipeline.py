"""Streaming end-cloud decode benchmark: pipelined vs serial step time.

Runs the same decode workload through

  * the single-tier continuous-batching ``ServingEngine`` (baseline), and
  * the streaming ``EndCloudServingEngine`` at the route-aware split, with
    the boundary double-buffered across two micro-batch groups,

and reports steady-state step times.  Stage compute times are measured on
this host; link times are modeled from the metered boundary bytes at the
configured bandwidth; the pipelined schedule is the resource-occupancy
timeline (same queueing model as ``repro.sim.simulator``).  The headline
check is the PO-ECC pipelining claim:

    pipelined_step_s  <  serial_step_s = t_end + t_comm + t_cloud
    pipelined_step_s  ->  max(t_end, t_comm, t_cloud)   (steady state)

A second phase degrades the end device's state mid-run to exercise dynamic
replanning (paper fig. 7's changing-load scenario): the engine re-splits
params and moves KV *pages* between the tier pools at a request-safe
boundary and keeps decoding.  (A pure bandwidth change with the codec off
does not move the split here: with the boundary shipped at every split,
wire cost is split-independent, and the replan hysteresis correctly refuses
a drain that buys nothing.)

A third phase admits one long prompt into a busy engine and asserts the
chunked-prefill claim: in-flight decode groups keep emitting tokens on
every tick of the prompt's prefill (admission is a pipeline stage streaming
through the same StageTimeline resources as decode, not a stop-the-world
event), and the engine compiles one trace per chunk/group shape, never one
per prompt length.

A separate MoE scenario (``run_expert``) exercises the paged expert-weight
pool under device-state degradation: the end device's memory budget halves
mid-run (the slab capacity follows it — residents are EVICTED at a safe
point, not merely routing-masked), then recovers (the re-grown expert set
is PREFETCHED, slab bytes booked on the same link timeline as boundary
traffic, overlapped with decode).  Asserted: expert hit rate above
threshold after each warmup, prefetch bytes actually booked on the link
resource, pipelined step < serial sum throughout, and per-step end-tier
expert HBM bytes <= 1/2 of the dense [E, d, f] sweep at the paper's 40%
selection cap.

Paged-KV memory accounting (``kv_pages_in_use`` / ``kv_bytes_peak`` /
``kv_utilization``) is reported alongside the dense ``max_batch x max_len``
equivalent, and the same live sample point checks the fused paged-attention
claim: per-decode-step attention KV bytes scale with *mapped pages*
(``attn_bytes_paged_step``), not slots x ring — the skewed batch must move
< 1/2 of the dense-gather bytes (``attn_bytes_dense_step``), asserted.

A final quantization phase (``run_quant``) replays the workload with the
int8 second-stage codecs on (KV pages, boundary payloads, expert slabs)
and asserts each metered byte stream lands at <= 0.55x its f32-path
counterpart, page/slab capacity >= 1.9x, and greedy decode matches the
unquantized engine within the documented tolerance.

    PYTHONPATH=src python -m benchmarks.decode_pipeline [--out BENCH_decode_pipeline.json]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.hardware import DeviceProfile, DeviceState
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.stream import EndCloudServingEngine
from repro.launch.cache import enable_compile_cache

# Device profiles calibrated to smoke-model scale (the paper-testbed profiles
# paired with a ~100k-param smoke model put every split in the all-cloud
# corner; these keep the planner in the interior regime the paper studies:
# end ~3x weaker than cloud, link fast enough that an interior split wins
# until the mid-run bandwidth drop).
END_SIM = DeviceProfile("end-sim", peak_gflops=2.0, mem_gb=8.0,
                        mem_bw_gbs=50.0, net_gbps=2.0)
CLOUD_SIM = DeviceProfile("cloud-sim", peak_gflops=6.0, mem_gb=80.0,
                          mem_bw_gbs=500.0, net_gbps=2.0)


def _requests(n: int, max_new_tokens: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [
        Request(i, rng.integers(0, 500, size=int(rng.integers(8, 24))).astype(np.int32),
                max_new_tokens=max_new_tokens)
        for i in range(n)
    ]


def run(
    *,
    arch: str = "tinyllama-1.1b",
    num_layers: int = 4,
    n_requests: int = 12,
    max_new_tokens: int = 24,
    max_batch: int = 8,
    compression_rank: int = 0,
    seed: int = 0,
) -> Dict:
    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    # -- baseline: single-tier continuous batching ---------------------------
    base = ServingEngine(model, params, max_batch=max_batch, max_len=128)
    for r in _requests(n_requests, max_new_tokens, seed):
        base.submit(r)
    t0 = time.perf_counter()
    base_done = base.run()
    base_wall = time.perf_counter() - t0
    base_tokens = sum(len(r.generated) for r in base_done)

    # -- streaming two-tier pipeline -----------------------------------------
    eng = EndCloudServingEngine(
        model, params,
        end_profile=END_SIM,
        cloud_profile=CLOUD_SIM,
        max_batch=max_batch, max_len=128,
        compression_rank=compression_rank,
    )
    reqs = _requests(n_requests, max_new_tokens, seed)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    stream_tokens = sum(len(r.generated) for r in done)
    m = eng.metrics()

    # -- dynamic load: the end device gets busy mid-run (fig. 7 scenario);
    # -- the replanner offloads blocks to the cloud at a safe point ----------
    replan_reqs = _requests(n_requests, max_new_tokens, seed + 1)
    for r in replan_reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    eng.update_device_state(DeviceState(cpu_free=0.05, power_free=0.1))
    eng.run()
    m2 = eng.metrics()

    # -- chunked prefill: a long prompt admitted mid-stream must not stall
    # -- the in-flight decode groups (no stop-the-world admission).  One
    # -- slot is left free for the long prompt; every other slot decodes a
    # -- long generation, and must keep emitting on every prefill tick. ----
    rng = np.random.default_rng(seed + 2)
    for r in _requests(eng.request_capacity - 1, 64, seed + 3):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    long_prompt = rng.integers(0, 500, size=96).astype(np.int32)
    long_req = Request(10_000, long_prompt, max_new_tokens=4)
    eng.submit(long_req)
    chunks_before = eng.metrics()["prefill_chunks"]
    stalled_ticks = prefill_ticks = 0
    while any(j.req is long_req for j in eng._jobs.values()) or eng.waiting:
        live = [r for r in eng.slots if r is not None]
        before = sum(len(r.generated) for r in live) + sum(
            len(r.generated) for r in eng.finished
        )
        eng.step()
        live = [r for r in eng.slots if r is not None]
        after = sum(len(r.generated) for r in live) + sum(
            len(r.generated) for r in eng.finished
        )
        prefill_ticks += 1
        if after == before:
            stalled_ticks += 1
    # sample KV occupancy while the batch is still live (after run() every
    # page is freed, so in-use/utilization would always read zero)
    kv_mid = eng.kv_metrics()
    # attention-bytes check, sampled at the same live point: the batch is
    # skewed (one 96-token prompt among short decodes), so the fused paged
    # sweep — which reads only *mapped* pages — must move well under half
    # of what the dense gather swept (slots x full ring, every step)
    assert 0 < kv_mid["attn_bytes_paged_step"] < kv_mid["attn_bytes_dense_step"], (
        kv_mid
    )
    assert kv_mid["attn_bytes_paged_step"] <= kv_mid["attn_bytes_dense_step"] / 2, (
        "paged attention must move < 1/2 the dense-gather KV bytes on a "
        f"skewed-length batch: {kv_mid}"
    )
    eng.run()
    m3 = eng.metrics()
    prefill_chunks = m3["prefill_chunks"] - chunks_before
    assert stalled_ticks == 0, (
        f"chunked prefill stalled decode for {stalled_ticks}/{prefill_ticks} "
        "ticks — admission must be a pipeline stage, not a stop-the-world event"
    )
    assert prefill_chunks >= len(long_prompt) // eng.prefill_chunk, (
        prefill_chunks, len(long_prompt), eng.prefill_chunk
    )
    # prefill chunks are StageTimeline occupancy on the same resources
    assert eng._prefill_busy["end"] > 0 and eng._prefill_busy["cloud"] > 0
    # compiled stage traces are bounded by chunk/group shapes (per stage-fn
    # rebuild), never by the number of distinct prompt lengths served
    traces = eng.stage_trace_counts()
    n_builds = eng._build_gen
    assert all(c <= n_builds for c in traces.values()), (traces, n_builds)

    row = {
        "arch": cfg.name,
        "block_repeat": cfg.block_repeat,
        "split": m["split"],
        "compressed": m["compressed"],
        "n_groups": m["n_groups"],
        "tokens_baseline": base_tokens,
        "tokens_streamed": stream_tokens,
        "baseline_wall_s": round(base_wall, 4),
        "stream_wall_s": round(wall, 4),
        "mean_t_end_s": round(m["mean_t_end_s"], 6),
        "mean_t_comm_s": round(m["mean_t_comm_s"], 6),
        "mean_t_cloud_s": round(m["mean_t_cloud_s"], 6),
        "serial_step_s": round(m["serial_step_s"], 6),
        "pipelined_step_s": round(m["pipelined_step_s"], 6),
        "max_stage_s": round(
            max(m["mean_t_end_s"], m["mean_t_comm_s"], m["mean_t_cloud_s"]), 6
        ),
        "plan_est_step_s": round(m["plan_est_step_s"], 6),
        "boundary_bytes_up": m["bytes_up"],
        "overlap_gain": round(m["serial_step_s"] / max(m["pipelined_step_s"], 1e-12), 3),
        "replan_events": m2["replan_events"],
        "split_after_load_spike": m2["split"],
        # paged KV-memory accounting (vs the dense max_batch x max_len
        # layout); in-use/utilization sampled mid-run with the batch live
        "kv_pages_in_use": kv_mid["kv_pages_in_use"],
        "kv_pages_capacity": kv_mid["kv_pages_capacity"],
        "kv_utilization": round(kv_mid["kv_utilization"], 4),
        "kv_bytes_peak": m3["kv_bytes_peak"],
        "kv_bytes_dense_equiv": m3["kv_bytes_dense_equiv"],
        # per-decode-step attention KV traffic at the skewed-batch sample
        # point: the fused paged kernel reads mapped pages only, the dense
        # gather it replaced swept slots x ring every step
        "attn_bytes_paged_step": kv_mid["attn_bytes_paged_step"],
        "attn_bytes_dense_step": kv_mid["attn_bytes_dense_step"],
        "attn_bytes_ratio": round(
            kv_mid["attn_bytes_paged_step"]
            / max(kv_mid["attn_bytes_dense_step"], 1), 4
        ),
        # chunked-prefill pipeline accounting
        "prefill_chunks": m3["prefill_chunks"],
        "long_prompt_prefill_ticks": prefill_ticks,
        "long_prompt_stalled_ticks": stalled_ticks,
        "stage_trace_counts": traces,
    }
    print(
        f"[decode_pipeline] split={row['split']}/{cfg.block_repeat} "
        f"serial={row['serial_step_s']*1e3:.2f}ms "
        f"pipelined={row['pipelined_step_s']*1e3:.2f}ms "
        f"(max stage {row['max_stage_s']*1e3:.2f}ms, x{row['overlap_gain']} overlap) "
        f"replans={row['replan_events']} -> split {row['split_after_load_spike']}",
        flush=True,
    )
    print(
        f"[decode_pipeline] kv peak {row['kv_bytes_peak']/1024:.1f}KiB "
        f"vs dense {row['kv_bytes_dense_equiv']/1024:.1f}KiB; "
        f"long-prompt prefill: {prefill_ticks} ticks, {stalled_ticks} stalled, "
        f"traces {traces}",
        flush=True,
    )
    print(
        f"[decode_pipeline] attention sweep "
        f"{row['attn_bytes_paged_step']/1024:.1f}KiB/step (mapped pages) "
        f"vs {row['attn_bytes_dense_step']/1024:.1f}KiB/step dense gather "
        f"(x{row['attn_bytes_ratio']} of dense on the skewed batch)",
        flush=True,
    )
    assert row["pipelined_step_s"] < row["serial_step_s"], (
        "pipelined decode must beat the serial sum of stage times"
    )
    return row


def run_expert(
    *,
    arch: str = "llama4-scout-17b-16e",
    num_layers: int = 4,
    n_requests: int = 8,
    max_new_tokens: int = 16,
    max_batch: int = 4,
    seed: int = 0,
) -> Dict:
    """Paged expert-weight pool under device-state degradation."""
    from repro.core.expertpool import expert_slab_bytes

    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    # split 1 is the planner's optimum at the END_SIM/CLOUD_SIM compute
    # ratio (s/2 = (R-s)/6 at R=4), so mid-run replan rechecks keep the
    # pinned split and the device-state changes exercise ONLY the expert
    # pool, not a tier re-split
    split = 1
    n_moe_pos = sum(1 for s in cfg.layer_pattern if s.moe)
    active = split * n_moe_pos
    cap_n = max(1, int(np.floor(cfg.moe.local_selection_cap * cfg.moe.num_experts)))
    slab = expert_slab_bytes(cfg)
    # memory sized so the full-state slab budget holds exactly the target
    # expert set on every end layer, and a mem_free=0.5 state halves it
    prof = DeviceProfile(
        "end-moe-sim", peak_gflops=END_SIM.peak_gflops,
        mem_gb=2 * active * cap_n * slab / 1e9,
        mem_bw_gbs=END_SIM.mem_bw_gbs, net_gbps=END_SIM.net_gbps,
    )
    eng = EndCloudServingEngine(
        model, params,
        end_profile=prof, cloud_profile=CLOUD_SIM,
        max_batch=max_batch, max_len=128, force_split=split,
    )
    for r in _requests(n_requests, max_new_tokens, seed):
        eng.submit(r)
    for _ in range(6):  # warmup decode
        eng.step()
    m0 = eng.metrics()
    assert m0["expert_hit_rate"] >= 0.95, m0["expert_hit_rate"]
    slabs_full = eng.expert_pool.slabs_in_use

    # -- degradation: memory budget halves -> slab capacity halves, the
    # -- resident set actually SHEDS experts (evictions at a safe point)
    eng.update_device_state(DeviceState(mem_free=0.5))
    for _ in range(6):
        eng.step()
    assert eng.n_expert_evictions > 0, "memory halving must evict slabs"
    assert eng.expert_pool.slabs_in_use < slabs_full
    for lid in eng._active_lids():
        assert eng.expert_pool.resident_count(lid) >= 1

    # -- recovery: the re-grown expert set is prefetched, slab bytes
    # -- booked on the link timeline while decode keeps stepping
    bytes_down_before = eng.expert_bytes_down
    eng.update_device_state(DeviceState(mem_free=1.0))
    for r in _requests(n_requests, max_new_tokens, seed + 1):
        eng.submit(r)
    eng.run()
    m = eng.metrics()
    prefetch_bytes = eng.expert_bytes_down - bytes_down_before
    assert m["expert_prefetches"] > 0 and prefetch_bytes > 0
    # prefetch wire time rides the shared link resource ON TOP of the
    # engine's own boundary/prefill seconds — overlapped with decode, and
    # the pipelining claim still holds
    own_link = eng._stage_busy["link"] + eng._prefill_busy["link"]
    assert eng.timeline.busy_s[eng._res_link] > own_link
    assert m["pipelined_step_s"] < m["serial_step_s"]
    assert m["expert_hit_rate"] >= 0.95, m["expert_hit_rate"]
    # acceptance: per-step expert HBM bytes scale with residents — at the
    # 40% selection cap, at most half the dense [E, d, f] sweep
    assert 0 < m["expert_bytes_step_resident"] <= m["expert_bytes_step_dense"] / 2

    row = {
        "arch": cfg.name,
        "split": m["split"],
        "expert_resident_slabs": m["expert_resident_slabs"],
        "expert_slab_capacity": m["expert_slab_capacity"],
        "expert_hit_rate": round(m["expert_hit_rate"], 4),
        "expert_prefetches": m["expert_prefetches"],
        "expert_evictions": m["expert_evictions"],
        "expert_bytes_down": m["expert_bytes_down"],
        "expert_bytes_up": m["expert_bytes_up"],
        "expert_bytes_step_resident": m["expert_bytes_step_resident"],
        "expert_bytes_step_dense": m["expert_bytes_step_dense"],
        "expert_bytes_ratio": round(
            m["expert_bytes_step_resident"]
            / max(m["expert_bytes_step_dense"], 1), 4
        ),
        "pipelined_step_s": round(m["pipelined_step_s"], 6),
        "serial_step_s": round(m["serial_step_s"], 6),
    }
    print(
        f"[decode_pipeline:experts] residents {row['expert_resident_slabs']}"
        f"/{row['expert_slab_capacity']} slabs, hit {row['expert_hit_rate']}, "
        f"{row['expert_evictions']} evictions on mem-halve, "
        f"{row['expert_prefetches']} prefetches "
        f"({row['expert_bytes_down']/1024:.1f}KiB on the link timeline), "
        f"step expert bytes x{row['expert_bytes_ratio']} of dense",
        flush=True,
    )
    return row


def run_quant(
    *,
    arch: str = "tinyllama-1.1b",
    moe_arch: str = "llama4-scout-17b-16e",
    num_layers: int = 4,
    n_requests: int = 8,
    max_new_tokens: int = 8,
    max_batch: int = 4,
    seed: int = 0,
) -> Dict:
    """Quantized byte streams: the same workload through the f32-path
    engine and the int8 engine (KV pages + boundary payloads + expert
    slabs), asserting the ~2x reduction on each stream and the greedy
    parity tolerance.  Dense baselines are priced at dense dtypes, so
    quantizing the storage must not move any denominator."""
    from repro.core.expertpool import expert_slab_bytes

    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rank = max(cfg.d_model // 4, 1)
    split = 2  # interior split: the boundary actually crosses the wire

    def drive(compression_rank, **quant):
        eng = EndCloudServingEngine(
            model, params,
            end_profile=END_SIM, cloud_profile=CLOUD_SIM,
            max_batch=max_batch, max_len=128,
            compression_rank=compression_rank, force_split=split, **quant,
        )
        for r in _requests(n_requests, max_new_tokens, seed):
            eng.submit(r)
        for _ in range(6):  # live sample point, identical tick in both runs
            eng.step()
        kv_mid = eng.kv_metrics()
        done = eng.run()
        toks = {r.request_id: list(r.generated) for r in done}
        return eng.metrics(), kv_mid, toks

    # parity pair: uncompressed boundary, so int8 quantization is the ONLY
    # perturbation between the two runs (at smoke scale the rank-r codec
    # itself leaves greedy logits near-tied, which would conflate codec
    # loss with quantization noise in the match rate)
    m_ref, kv_ref, tok_ref = drive(0)
    m_q, kv_q, tok_q = drive(0, quantize_kv=True, quantize_boundary=True)

    assert set(tok_q) == set(tok_ref)
    total = sum(len(t) for t in tok_ref.values())
    matched = sum(
        int(a == b)
        for rid in tok_ref
        for a, b in zip(tok_ref[rid], tok_q[rid])
    )
    match_rate = matched / max(total, 1)
    # boundary stream: int8 codes + one f16 scale per row after the rank-r
    # encode -> (r + 2) / (2 r) of the f32-path payload
    up_ratio = m_q["bytes_up"] / max(m_ref["bytes_up"], 1)
    # attention stream at the same live tick: identical mapped pages, int8
    # K/V plus the per-token f16 scale sidecar riding the page table
    attn_ratio = (
        kv_q["attn_bytes_paged_step"] / max(kv_ref["attn_bytes_paged_step"], 1)
    )
    assert 0 < up_ratio <= 0.55, f"boundary bytes ratio {up_ratio}"
    assert 0 < attn_ratio <= 0.55, f"attention bytes ratio {attn_ratio}"
    assert kv_q["kv_capacity_ratio"] >= 1.9, kv_q["kv_capacity_ratio"]
    assert kv_ref["kv_capacity_ratio"] == 1.0, kv_ref["kv_capacity_ratio"]
    assert kv_q["attn_bytes_dense_step"] == kv_ref["attn_bytes_dense_step"]
    assert match_rate >= 0.85, (
        f"quantized greedy decode matched only {matched}/{total} tokens"
    )

    # codec composition: the quantizer is a SECOND stage after the rank-r
    # low-rank encode — int8 codes + f16 scale over r components lands at
    # (r + 2) / (2 r) of the compressed f32-path payload
    m_cref, _, _ = drive(rank)
    m_cq, _, tok_cq = drive(
        rank, quantize_kv=True, quantize_boundary=True)
    comp_ratio = m_cq["bytes_up"] / max(m_cref["bytes_up"], 1)
    assert 0 < comp_ratio <= 0.55, f"compressed boundary ratio {comp_ratio}"
    assert sum(len(t) for t in tok_cq.values()) == total  # no stall/loss

    # -- expert-weight stream (MoE): halve -> recover so the re-grown set
    # -- is PREFETCHED and bytes_down meters real slab wire in both runs.
    # -- The budget is sized in the engine's own STORED slab size so both
    # -- runs hold the same slab count and the ratio isolates bytes/slab.
    cfg_e = smoke_config(get_config(moe_arch)).replace(num_layers=4)
    model_e = build_model(cfg_e)
    params_e = model_e.init(jax.random.PRNGKey(seed))
    n_moe = sum(1 for s in cfg_e.layer_pattern if s.moe)
    cap_n = max(1, int(np.floor(
        cfg_e.moe.local_selection_cap * cfg_e.moe.num_experts)))

    def drive_expert(qe):
        slab = expert_slab_bytes(cfg_e, quantized=qe)
        prof = DeviceProfile(
            "end-moe-sim", peak_gflops=END_SIM.peak_gflops,
            mem_gb=2 * n_moe * cap_n * slab / 1e9,
            mem_bw_gbs=END_SIM.mem_bw_gbs, net_gbps=END_SIM.net_gbps,
        )
        eng = EndCloudServingEngine(
            model_e, params_e,
            end_profile=prof, cloud_profile=CLOUD_SIM,
            max_batch=max_batch, max_len=128, force_split=1,
            quantize_experts=qe,
        )
        for r in _requests(n_requests, max_new_tokens, seed):
            eng.submit(r)
        for _ in range(4):
            eng.step()
        eng.update_device_state(DeviceState(mem_free=0.5))
        for _ in range(4):
            eng.step()
        b0 = eng.expert_bytes_down
        eng.update_device_state(DeviceState(mem_free=1.0))
        eng.run()
        return eng.metrics(), eng.expert_bytes_down - b0

    me_ref, pf_ref = drive_expert(False)
    me_q, pf_q = drive_expert(True)
    assert pf_ref > 0 and pf_q > 0, (pf_ref, pf_q)
    down_ratio = pf_q / pf_ref
    assert down_ratio <= 0.55, f"expert slab wire ratio {down_ratio}"
    assert me_q["expert_capacity_ratio"] >= 1.9, me_q["expert_capacity_ratio"]
    assert me_ref["expert_capacity_ratio"] == 1.0
    assert me_q["expert_bytes_step_dense"] == me_ref["expert_bytes_step_dense"]

    row = {
        "phase": "quantized_streams",
        "arch": cfg.name,
        "moe_arch": cfg_e.name,
        "split": split,
        "compression_rank": rank,
        "greedy_match_rate": round(match_rate, 4),
        "boundary_bytes_up": m_q["bytes_up"],
        "boundary_bytes_up_f32path": m_ref["bytes_up"],
        "boundary_bytes_ratio": round(up_ratio, 4),
        "boundary_bytes_ratio_compressed": round(comp_ratio, 4),
        "attn_bytes_paged_step": kv_q["attn_bytes_paged_step"],
        "attn_bytes_paged_step_f32path": kv_ref["attn_bytes_paged_step"],
        "attn_bytes_quant_ratio": round(attn_ratio, 4),
        "kv_capacity_ratio": round(kv_q["kv_capacity_ratio"], 4),
        "expert_prefetch_bytes_down": pf_q,
        "expert_prefetch_bytes_down_f32path": pf_ref,
        "expert_bytes_quant_ratio": round(down_ratio, 4),
        "expert_capacity_ratio": round(me_q["expert_capacity_ratio"], 4),
    }
    print(
        f"[decode_pipeline:quant] greedy match {matched}/{total} "
        f"({row['greedy_match_rate']}); bytes ratios: "
        f"boundary x{row['boundary_bytes_ratio']} "
        f"(x{row['boundary_bytes_ratio_compressed']} after rank-{rank} encode), "
        f"attention x{row['attn_bytes_quant_ratio']}, "
        f"expert slabs x{row['expert_bytes_quant_ratio']}; "
        f"capacity: kv x{row['kv_capacity_ratio']}, "
        f"experts x{row['expert_capacity_ratio']}",
        flush=True,
    )
    return row


def run_spec(
    *,
    arch: str = "tinyllama-1.1b",
    num_layers: int = 4,
    n_requests: int = 6,
    max_new_tokens: int = 16,
    max_batch: int = 4,
    link_rtt_ms: float = 60.0,
    spec_k: int = 8,
    seed: int = 0,
) -> Dict:
    """Speculative multi-token decode across a link-bound boundary.

    In the RTT-dominated regime every non-speculative decode round pays
    one link round trip for one token; the end tier drafting k tokens and
    the cloud verifying them in one C=k chunk amortizes that round trip
    over the accepted prefix.  Asserted:

      * greedy tokens bit-identical to the non-speculative engine at
        splits 0 / mid / R (the rollback-and-correct rule makes parity
        structural, not statistical — f32 config so argmax ties are
        deterministic across the chunked and decode paths);
      * >= 1.4x tokens per boundary round trip at acceptance >= 0.6, and
        a shorter modeled decode span, in the link-bound scenario;
      * with the RTT override at 0 (compute-bound), the planner
        auto-disables speculation (k=1): zero spec rounds, and the step
        count matches the plain engine exactly — no overhead.
    """
    from repro.serving.common import VirtualClock

    cfg = smoke_config(get_config(arch)).replace(
        num_layers=num_layers, dtype="float32"
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    R = cfg.block_repeat
    rtt_s = link_rtt_ms * 1e-3

    def drive(split, k, rtt):
        eng = EndCloudServingEngine(
            model, params,
            end_profile=END_SIM, cloud_profile=CLOUD_SIM,
            max_batch=max_batch, max_len=64, force_split=split,
            timing="modeled", clock=VirtualClock(),
            spec_k=k, link_rtt_s=rtt,
        )
        reqs = _requests(n_requests, max_new_tokens, seed)
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        m = eng.metrics()
        toks = {r.request_id: list(r.generated) for r in done}
        return toks, m

    # -- exact-greedy-parity contract at splits 0 / mid / R ------------------
    for split in (0, R // 2, R):
        tok_ref, _ = drive(split, 1, rtt_s)
        tok_spec, m_s = drive(split, spec_k, rtt_s)
        assert tok_spec == tok_ref, (
            f"speculative greedy tokens diverged at split {split}"
        )
        assert m_s["spec_rounds"] > 0, (
            f"link-bound run at split {split} never speculated: {m_s}"
        )

    # -- link-bound speedup: tokens per boundary round trip ------------------
    split = R // 2
    tok_base, m_base = drive(split, 1, rtt_s)
    tok_spec, m_spec = drive(split, spec_k, rtt_s)
    tokens = sum(len(t) for t in tok_base.values())
    base_tpr = tokens / max(m_base["n_stage_steps"], 1)
    spec_tpr = tokens / max(m_spec["n_stage_steps"], 1)
    speedup = spec_tpr / max(base_tpr, 1e-12)
    acceptance = m_spec["spec_acceptance_rate"]
    assert acceptance >= 0.6, (
        f"acceptance {acceptance} < 0.6 — the dense draft should be exact"
    )
    assert speedup >= 1.4, (
        f"tokens per boundary round trip improved only x{speedup:.2f} "
        f"({base_tpr:.2f} -> {spec_tpr:.2f}) at acceptance {acceptance}"
    )
    # and the modeled decode span (RTT rides every link occupancy) shrinks
    assert m_spec["pipelined_total_s"] < m_base["pipelined_total_s"], (
        m_spec["pipelined_total_s"], m_base["pipelined_total_s"],
    )

    # -- compute-bound regime: speculation must auto-disable, zero overhead --
    tok_cb_ref, m_cb_ref = drive(split, 1, 0.0)
    tok_cb, m_cb = drive(split, spec_k, 0.0)
    assert m_cb["spec_plan_k"] == 1, m_cb["spec_plan_k"]
    assert m_cb["spec_rounds"] == 0
    assert tok_cb == tok_cb_ref
    assert m_cb["n_stage_steps"] == m_cb_ref["n_stage_steps"], (
        m_cb["n_stage_steps"], m_cb_ref["n_stage_steps"],
    )

    row = {
        "phase": "speculative_decode",
        "arch": cfg.name,
        "split": split,
        "link_rtt_ms": link_rtt_ms,
        "spec_k_budget": spec_k,
        "spec_plan_k": m_spec["spec_plan_k"],
        "spec_k_eff": m_spec["spec_k_eff"],
        "spec_rounds": m_spec["spec_rounds"],
        "spec_drafted": m_spec["spec_drafted"],
        "spec_accepted": m_spec["spec_accepted"],
        "spec_acceptance_rate": acceptance,
        "spec_rollbacks": m_spec["spec_rollbacks"],
        "tokens": tokens,
        "base_tokens_per_round": round(base_tpr, 4),
        "spec_tokens_per_round": round(spec_tpr, 4),
        "spec_speedup": round(speedup, 3),
        "base_decode_span_s": round(m_base["pipelined_total_s"], 4),
        "spec_decode_span_s": round(m_spec["pipelined_total_s"], 4),
        "computebound_plan_k": m_cb["spec_plan_k"],
        "greedy_parity": 1.0,
        "n_host_syncs": m_spec["n_host_syncs"],
        "n_host_syncs_base": m_base["n_host_syncs"],
    }
    print(
        f"[decode_pipeline:spec] rtt={link_rtt_ms}ms k={row['spec_plan_k']} "
        f"(eff {row['spec_k_eff']}): {row['base_tokens_per_round']} -> "
        f"{row['spec_tokens_per_round']} tokens/round (x{row['spec_speedup']}) "
        f"at acceptance {acceptance}, decode span "
        f"{row['base_decode_span_s']}s -> {row['spec_decode_span_s']}s; "
        f"compute-bound plan k={row['computebound_plan_k']} (auto-disabled), "
        f"greedy parity exact at splits 0/{R // 2}/{R}",
        flush=True,
    )
    return row


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_decode_pipeline.json")
    ap.add_argument("--rank", type=int, default=0)
    # tiny-shape knobs so CI can smoke the overlap / no-stall assertions
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    # link-bound speculative-decode scenario (bandwidth-constrained round
    # trips; 0 disables the scenario's RTT and exercises only auto-disable)
    ap.add_argument("--link-rtt-ms", type=float, default=60.0)
    ap.add_argument("--spec-k", type=int, default=8)
    args = ap.parse_args()
    rows = [run(
        compression_rank=args.rank,
        num_layers=args.layers,
        n_requests=args.requests,
        max_new_tokens=args.new_tokens,
        max_batch=args.max_batch,
    )]
    rows.append(run_expert(
        num_layers=4,  # R=4 puts the planner's optimum at split 1
        n_requests=args.requests,
        max_new_tokens=args.new_tokens,
        max_batch=args.max_batch,
    ))
    rows.append(run_quant(
        num_layers=4,  # interior split 2 of R=4 puts the boundary on the wire
        max_batch=min(args.max_batch, 4),
    ))
    rows.append(run_spec(
        num_layers=args.layers,
        max_batch=min(args.max_batch, 4),
        link_rtt_ms=args.link_rtt_ms,
        spec_k=args.spec_k,
    ))
    json.dump(rows, open(args.out, "w"), indent=1)
    # stable machine-readable artifact name for CI collection, regardless
    # of --out
    if args.out != "BENCH_decode_pipeline.json":
        json.dump(rows, open("BENCH_decode_pipeline.json", "w"), indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
