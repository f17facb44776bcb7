"""Fleet serving benchmark: aggregate throughput vs number of end devices.

Serves one fixed request workload through the heterogeneous multi-end
fleet engine (``serving.fleet.FleetServingEngine``) with 1..N end devices
— including one deliberate straggler (weak compute, slow link) — against
one shared cloud tier, and reports the modeled aggregate decode rate
(``aggregate_tokens_per_s``: total generated tokens over the fleet-wide
resource-occupancy makespan, the same queueing model as
``sim.simulator``).  The paper's scalability claim at serving level:

    for a fixed offered workload, aggregate tokens/s grows monotonically
    as end devices are added — route-aware placement spreads requests over
    the new device's end+link stages, the shared cloud being the only
    contended resource,

and the fleet degrades *gracefully* under per-device drift: phase 2 cuts
one device's bandwidth mid-run — only that device replans (at its own
drained safe point, recorded in ``replan_events``, landing on a
compressed interior split) and every request still completes (no stall).

Phase 3 exercises the paged expert-weight pool at fleet scale on an MoE
model: one lane's memory budget halves mid-run — its slab capacity
follows, its resident expert set shrinks via EVICTIONS at that lane's own
safe point (every end layer keeps at least one resident), and the fleet
keeps serving: every request completes, no other lane evicts or replans,
aggregate tok/s stays positive.

Phase 4 exercises the fleet-wide expert store under skewed routing: two
lanes' measured traffic drifts to *overlapping* expert groups, so one
lane's slab misses are served from the peer that already fetched them —
over the modeled end<->end LAN, booked on BOTH lanes' link timelines —
while the divergent remainder keeps fleet-wide unique residency well
above any single lane's slab capacity, and the peer-served slabs come
off the cloud downlink (strictly fewer ``expert_bytes_down`` than the
isolated-pools baseline on the same trace).

Tokens are computed for real; stage times use ``timing="modeled"`` (the
planner's capability cost model) because one host cannot exhibit four
declared device speeds — which also makes the run deterministic.

    PYTHONPATH=src python -m benchmarks.fleet_throughput [--out bench_fleet.json]
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.hardware import DeviceProfile
from repro.models.model import build_model
from repro.serving.common import Request
from repro.serving.fleet import FleetServingEngine
from repro.launch.cache import enable_compile_cache

# Smoke-scale fleet: three device classes plus one straggler, against a
# deliberately *scarce* shared cloud (the fleet regime the paper's
# scalability claim lives in).  Calibrated so the per-device planners put
# real compute on the end tiers — strong/mid devices plan end-heavy (often
# all-end) splits against their 1/N cloud share, the straggler plans
# cloud-heavy — because throughput can only scale with devices if the
# added devices' end resources carry work.
FLEET_PROFILES = [
    DeviceProfile("end-strong", peak_gflops=8.0, mem_gb=16.0,
                  mem_bw_gbs=100.0, net_gbps=2.0),
    DeviceProfile("end-mid", peak_gflops=6.0, mem_gb=8.0,
                  mem_bw_gbs=50.0, net_gbps=1.0),
    DeviceProfile("end-mid", peak_gflops=6.0, mem_gb=8.0,
                  mem_bw_gbs=50.0, net_gbps=1.0),
    DeviceProfile("end-straggler", peak_gflops=2.0, mem_gb=4.0,
                  mem_bw_gbs=25.0, net_gbps=0.25),
]
CLOUD = DeviceProfile("cloud-sim", peak_gflops=4.0, mem_gb=80.0,
                      mem_bw_gbs=500.0, net_gbps=2.0)


def _requests(n: int, max_new_tokens: int, seed: int = 0) -> List[Request]:
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
    n_requests: int = 48,
    max_new_tokens: int = 16,
    max_batch: int = 2,
    cloud_servers: int = 1,
    seed: int = 0,
    # Tight spill guard: chunked prefill books prompt compute on the
    # timeline honestly, so handing the straggler more work than its
    # end-tier prefill time is worth would sink the n=4 scaling point
    # (the seed's looser default guard predates prefill accounting).
    max_spill: float = 1.0,
) -> Dict:
    cfg = smoke_config(get_config(arch)).replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rank = max(cfg.d_model // 4, 1)  # eq. 8 boundary codec (interior splits)

    n_max = len(FLEET_PROFILES)
    scaling = []
    for n in range(1, n_max + 1):
        eng = FleetServingEngine(
            model, params,
            end_profiles=FLEET_PROFILES[:n],
            cloud_profile=CLOUD,
            cloud_servers=cloud_servers,
            compression_rank=rank,
            max_batch=max_batch, max_len=128,
            timing="modeled", max_spill=max_spill,
        )
        for r in _requests(n_requests, max_new_tokens, seed):
            eng.submit(r)
        done = eng.run()
        m = eng.metrics()
        assert len(done) == n_requests, (len(done), n)
        placed = [0] * n
        for ev in eng.placed:
            placed[ev["device"]] += 1
        assert m["kv_pages_in_use"] == 0, "pages leaked after drain"
        scaling.append({
            "n_devices": n,
            "splits": m["splits"],
            "requests_per_device": placed,
            "tokens": m["tokens"],
            "fleet_makespan_s": round(m["fleet_makespan_s"], 4),
            "aggregate_tokens_per_s": round(m["aggregate_tokens_per_s"], 2),
            # fleet-wide paged KV: per-lane end pools + one shared cloud pool
            "kv_pages_capacity": m["kv_pages_capacity"],
            "kv_bytes_peak": m["kv_bytes_peak"],
        })
        print(
            f"[fleet_throughput] n={n} splits={m['splits']} placed={placed} "
            f"tokens={m['tokens']} "
            f"agg={m['aggregate_tokens_per_s']:.1f} tok/s "
            f"kv_peak={m['kv_bytes_peak']/1024:.1f}KiB",
            flush=True,
        )

    rates = [row["aggregate_tokens_per_s"] for row in scaling]
    for a, b in zip(rates, rates[1:]):
        assert b > a, f"fleet throughput must scale with devices: {rates}"

    # -- phase 2: cut one device's bandwidth mid-run (fig. 8 dynamics at
    # -- fleet scale) — only that device replans; nothing stalls ------------
    eng = FleetServingEngine(
        model, params,
        end_profiles=FLEET_PROFILES,
        cloud_profile=CLOUD,
        cloud_servers=cloud_servers,
        compression_rank=rank,
        max_batch=max_batch, max_len=128,
        timing="modeled", max_spill=max_spill,
    )
    # Cut a lane serving an *edge* split (boundary shipped uncompressed —
    # the codec only applies interior): once the wire cost dwarfs compute,
    # the replanner moves that lane to a compressed interior split.  Lanes
    # already on interior compressed splits are bandwidth-stable by design
    # (wire cost is split-independent there; see benchmarks.decode_pipeline).
    R = cfg.block_repeat
    cut_dev = next(i for i, l in enumerate(eng.lanes) if l.split in (0, R))
    for r in _requests(n_requests, max_new_tokens, seed + 1):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    lane = eng.lanes[cut_dev]
    old_split = lane.split
    # self-calibrating cut: make the uncompressed boundary ~40x the lane's
    # modeled bottleneck stage, so the compressed-interior plan clears the
    # replan hysteresis by construction
    t_ref = max(lane.plan.est_step_time_s, 1e-9)
    gbps_cut = lane.tiers.boundary_bytes * 8.0 / (40.0 * t_ref) / 1e9
    eng.observe_bandwidth(cut_dev, gbps_cut)
    done = eng.run()
    m2 = eng.metrics()
    events = eng.replan_events
    assert len(done) == n_requests, "bandwidth cut stalled the fleet"
    assert any(ev["device"] == cut_dev for ev in events), (
        "bandwidth cut must trigger a replan on the cut device"
    )
    assert all(ev["device"] == cut_dev for ev in events), (
        "only the drifted device may replan"
    )
    assert 0 < eng.lanes[cut_dev].split < R and eng.lanes[cut_dev].tiers.compress, (
        "cut lane should land on a compressed interior split"
    )

    # -- phase 3: paged expert weights under a per-lane memory cut (MoE
    # -- model) — one lane's slab budget halves, its resident set shrinks
    # -- via evictions, nothing else stalls ----------------------------------
    expert_row = _run_expert_memory_cut(
        n_requests=max(n_requests // 2, 8),
        max_new_tokens=max_new_tokens,
        max_batch=max_batch,
        cloud_servers=cloud_servers,
        max_spill=max_spill,
        seed=seed,
    )

    # -- phase 4: fleet expert store — skewed routes, peer slab fetch,
    # -- fleet-wide de-duplicated residency ----------------------------------
    fleet_store_row = _run_fleet_expert_store(
        n_requests=max(n_requests // 4, 8),
        max_new_tokens=max_new_tokens,
        max_batch=max_batch,
        cloud_servers=cloud_servers,
        max_spill=max_spill,
        seed=seed,
    )

    # -- phase 5: quantized byte streams at fleet scale — the same MoE
    # -- trace with the int8 codecs off and on; boundary wire, KV pages,
    # -- and re-admitted expert slabs must each shrink ~2x ------------------
    quant_row = _run_quant_fleet(
        n_requests=max(n_requests // 4, 8),
        max_new_tokens=max_new_tokens,
        max_batch=max_batch,
        cloud_servers=cloud_servers,
        max_spill=max_spill,
        seed=seed,
    )

    row = {
        "arch": cfg.name,
        "block_repeat": cfg.block_repeat,
        "cloud_servers": cloud_servers,
        "compression_rank": rank,
        "scaling": scaling,
        "expert_memory_cut": expert_row,
        "fleet_expert_store": fleet_store_row,
        "quantized_streams": quant_row,
        "bandwidth_cut": {
            "device": cut_dev,
            "gbps_cut": gbps_cut,
            "replan_events": events,
            "splits_after": m2["splits"],
            "aggregate_tokens_per_s": round(m2["aggregate_tokens_per_s"], 2),
            # peak only: the fleet is drained here, so instantaneous
            # in-use/utilization would always read zero
            "kv_bytes_peak": m2["kv_bytes_peak"],
        },
    }
    print(
        f"[fleet_throughput] dev{cut_dev} bw cut {gbps_cut:.2e} gbps -> "
        f"{len(events)} replan(s), split {old_split}->{eng.lanes[cut_dev].split}, "
        f"splits {m2['splits']}, agg={m2['aggregate_tokens_per_s']:.1f} tok/s "
        f"(all requests done)",
        flush=True,
    )
    return row


def _run_expert_memory_cut(
    *,
    n_requests: int,
    max_new_tokens: int,
    max_batch: int,
    cloud_servers: int,
    max_spill: float,
    seed: int,
) -> Dict:
    from repro.core.expertpool import expert_slab_bytes
    from repro.core.hardware import DeviceState

    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    slab = expert_slab_bytes(cfg)
    cap_n = max(1, int(cfg.moe.local_selection_cap * cfg.moe.num_experts))
    n_pos = sum(1 for s in cfg.layer_pattern if s.moe)

    def build(mems, force_splits=None):
        profiles = [
            DeviceProfile(f"end-moe{i}", peak_gflops=p.peak_gflops,
                          mem_gb=mems[i], mem_bw_gbs=p.mem_bw_gbs,
                          net_gbps=p.net_gbps)
            for i, p in enumerate(FLEET_PROFILES[:2])
        ]
        return FleetServingEngine(
            model, params,
            end_profiles=profiles, cloud_profile=CLOUD,
            cloud_servers=cloud_servers,
            max_batch=max_batch, max_len=128,
            timing="modeled", max_spill=max_spill,
            force_splits=force_splits,
        )

    # probe pass: memory never enters the split search, so the planner's
    # splits with generous memory ARE the optima — pin them in the real
    # pass so mid-run mask rechecks cannot move a tier boundary and the
    # memory cut exercises only the expert pool
    splits = [lane.split for lane in build([1.0, 1.0]).lanes]
    # lane memory sized so the full-state slab budget exactly covers each
    # lane's target expert set, and a mem_free=0.5 state halves it
    mems = [2 * max(s, 1) * n_pos * cap_n * slab / 1e9 for s in splits]
    eng = build(mems, force_splits=splits)

    for r in _requests(n_requests, max_new_tokens, seed + 2):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    cut = 1
    lane = eng.lanes[cut]
    slabs_before = lane.expert_pool.slabs_in_use
    eng.update_device_state(cut, DeviceState(mem_free=0.5))
    done = eng.run()
    m = eng.metrics()

    assert len(done) == n_requests, "memory cut stalled the fleet"
    assert lane.n_expert_evictions > 0, "halved budget must evict slabs"
    assert lane.expert_pool.capacity < slabs_before
    assert lane.expert_pool.peak_in_use == slabs_before
    for lid in lane._active_lids():
        assert lane.expert_pool.resident_count(lid) >= 1
    other = eng.lanes[1 - cut]
    assert other.n_expert_evictions == 0, "only the cut lane may evict"
    assert not any(
        ev["mask_changed"] for ev in other.replan_events
    ), "only the cut lane's expert set may change"
    assert [lane.split for lane in eng.lanes] == splits, (
        "the memory cut must not move a tier boundary"
    )
    assert m["aggregate_tokens_per_s"] > 0

    row = {
        "splits": splits,
        "cut_device": cut,
        "slabs_before": slabs_before,
        "slabs_after": lane.expert_pool.slabs_in_use,
        "capacity_after": lane.expert_pool.capacity,
        "evictions": lane.n_expert_evictions,
        "fleet_hit_rate": round(m["expert_hit_rate"], 4),
        "aggregate_tokens_per_s": round(m["aggregate_tokens_per_s"], 2),
    }
    print(
        f"[fleet_throughput] dev{cut} mem halved -> slabs "
        f"{slabs_before}->{row['slabs_after']} "
        f"(capacity {row['capacity_after']}, {row['evictions']} evictions), "
        f"splits {splits} unchanged, "
        f"agg={row['aggregate_tokens_per_s']:.1f} tok/s (all requests done)",
        flush=True,
    )
    return row


def _run_fleet_expert_store(
    *,
    n_requests: int,
    max_new_tokens: int,
    max_batch: int,
    cloud_servers: int,
    max_spill: float,
    seed: int,
) -> Dict:
    """Skewed-route fleet on an MoE model: lane 0's traffic drifts to
    groups {2,3}, lane 1's to {1,2}.  Lane 1's misses on the shared group
    2 experts are served from lane 0 over the modeled end<->end LAN; the
    divergent remainder keeps the fleet-wide unique resident set >= 1.5x
    any single lane's slab capacity."""
    from repro.core.expertpool import expert_slab_bytes
    from repro.core.hardware import DeviceState

    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    slab = expert_slab_bytes(cfg)
    E, K = cfg.moe.num_experts, cfg.moe.num_groups
    Mk = E // K
    cap_n = max(1, int(cfg.moe.local_selection_cap * E))
    n_pos = sum(1 for s in cfg.layer_pattern if s.moe)

    def build(mems, force_splits=None, expert_fleet=True):
        profiles = [
            DeviceProfile(f"end-moe{i}", peak_gflops=p.peak_gflops,
                          mem_gb=mems[i], mem_bw_gbs=p.mem_bw_gbs,
                          net_gbps=p.net_gbps)
            for i, p in enumerate(FLEET_PROFILES[:2])
        ]
        return FleetServingEngine(
            model, params,
            end_profiles=profiles, cloud_profile=CLOUD,
            cloud_servers=cloud_servers,
            max_batch=max_batch, max_len=128,
            timing="modeled", max_spill=max_spill,
            force_splits=force_splits, expert_fleet=expert_fleet,
            expert_peer_gbps=25.0,  # fleet LAN >> either WAN uplink
            expert_prefetch_per_tick=4, preemption=False,
        )

    # probe pass: the pinned splits must be the planner's own optima —
    # a device-state update re-runs the split search, and a boundary move
    # would re-base layer ids and instant-fill entering blocks (phase 3's
    # pattern; memory never enters the split search so generous probe
    # memory finds the same splits)
    splits = [lane.split for lane in build([1.0, 1.0]).lanes]
    # lane memory sized so the slab budget exactly covers each lane's
    # target expert set: divergent masks then cannot hide behind slack
    # capacity — residency must actually swap via evictions
    mems = [2 * max(s, 1) * n_pos * cap_n * slab / 1e9 for s in splits]

    # measured traffic skew, injected as the engines' EMA state: group
    # frequencies steer the eq. 4 admit, expert frequencies clear the
    # registry's 1/E replication bar for the experts each lane re-admits
    # 0.8/0.2: the gap must exceed the bounded group-cost term (0.5 after
    # normalization), or the registry's cheap-to-place signal would
    # reorder the admit toward the peer-resident group and shrink the
    # divergence this scenario is built to show
    def skew(groups):
        gf = np.zeros(K)
        gf[groups[0]], gf[groups[1]] = 0.8, 0.2
        mask_e = [g * Mk + j for g in groups for j in range(Mk)]
        ef = np.zeros(E)
        ef[mask_e] = 1.0 / len(mask_e)
        return gf, ef

    def drive(expert_fleet):
        eng = build(mems, force_splits=splits, expert_fleet=expert_fleet)
        for r in _requests(n_requests, max_new_tokens, seed + 3):
            eng.submit(r)
        for _ in range(2):
            eng.step()
        # lane 0 drifts first: groups {2,3} — every re-admitted slab comes
        # from the cloud (no peer holds them yet)
        gf, ef = skew((2, 3))
        eng.lanes[0]._group_freq, eng.lanes[0]._route_freq = gf, ef
        eng.update_device_state(0, DeviceState())
        for _ in range(8):
            eng.step()
        # lane 1 follows onto overlapping groups {1,2}: its misses on the
        # shared group-2 experts are now peer-resident on lane 0
        gf, ef = skew((1, 2))
        eng.lanes[1]._group_freq, eng.lanes[1]._route_freq = gf, ef
        eng.update_device_state(1, DeviceState())
        done = eng.run()
        assert len(done) == n_requests, "expert-store phase stalled the fleet"
        return eng

    eng = drive(expert_fleet=True)
    iso = drive(expert_fleet=False)
    m, mi = eng.metrics(), iso.metrics()
    reg = eng.expert_registry

    # peer fetch happened, and every transfer flowed lane 0 -> lane 1
    assert m["expert_peer_fetches"] > 0, "no slab was served from a peer"
    assert all((s, d) == (0, 1) for s, d, _ in reg.peer_bookings)
    # both ends of each peer transfer ride the fleet timeline: a lane's
    # link busy time is its own boundary/prefill/slab traffic plus the
    # peer seconds it served as a source
    for i, lane in enumerate(eng.lanes):
        peer_out = sum(t for s, _d, t in reg.peer_bookings if s == i)
        own = (lane._stage_busy["link"] + lane._prefill_busy["link"]
               + lane.expert_wire_s)
        assert abs(eng.timeline.busy_s[f"link{i}"] - (own + peer_out)) < 1e-9
    # divergent masks: fleet-wide unique residency beats any single lane's
    # slab capacity by >= 1.5x, yet the shared experts are still held once
    # per interested lane (unique < summed residents)
    unique = m["expert_unique_residents"]
    max_cap = max(lane.expert_pool.capacity for lane in eng.lanes)
    assert unique >= 1.5 * max_cap, (unique, max_cap)
    assert unique < m["expert_resident_slabs"]
    # the peer-served slabs came off the cloud downlink: strictly fewer
    # cloud bytes than the isolated-pools baseline on the SAME trace
    assert mi["expert_peer_fetches"] == 0
    assert m["expert_bytes_down"] < mi["expert_bytes_down"], (
        m["expert_bytes_down"], mi["expert_bytes_down"]
    )
    assert m["aggregate_tokens_per_s"] > 0

    row = {
        "splits": splits,
        "unique_residents": unique,
        "resident_slabs": m["expert_resident_slabs"],
        "dedup_ratio": round(m["expert_fleet_dedup_ratio"], 4),
        "max_lane_capacity": max_cap,
        "peer_fetches": m["expert_peer_fetches"],
        "bytes_peer": m["expert_bytes_peer"],
        "bytes_down": m["expert_bytes_down"],
        "bytes_down_isolated": mi["expert_bytes_down"],
        "fleet_hit_rate": round(m["expert_hit_rate"], 4),
        "aggregate_tokens_per_s": round(m["aggregate_tokens_per_s"], 2),
    }
    print(
        f"[fleet_throughput] fleet expert store: "
        f"{row['peer_fetches']} peer fetch(es) "
        f"({row['bytes_peer']/1024:.0f}KiB off the cloud downlink, "
        f"down {row['bytes_down']/1024:.0f}KiB vs "
        f"{row['bytes_down_isolated']/1024:.0f}KiB isolated), "
        f"unique residents {unique} vs lane capacity {max_cap} "
        f"(dedup ratio {row['dedup_ratio']}), "
        f"agg={row['aggregate_tokens_per_s']:.1f} tok/s (all requests done)",
        flush=True,
    )
    return row


def _run_quant_fleet(
    *,
    n_requests: int,
    max_new_tokens: int,
    max_batch: int,
    cloud_servers: int,
    max_spill: float,
    seed: int,
) -> Dict:
    """Quantized byte streams at fleet scale: the same skewed-route MoE
    trace with the int8 codecs off and on.  Boundary wire, KV page bytes,
    and re-admitted expert slabs (priced by the fleet registry at the
    STORED slab size) must each land at <= 0.55x the f32-path run; page
    and slab capacity must be >= 1.9x at the same memory budget."""
    from repro.core.expertpool import expert_slab_bytes
    from repro.core.hardware import DeviceState

    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    E, K = cfg.moe.num_experts, cfg.moe.num_groups
    Mk = E // K
    cap_n = max(1, int(cfg.moe.local_selection_cap * E))
    n_pos = sum(1 for s in cfg.layer_pattern if s.moe)

    def build(mems, force_splits=None, quant=False):
        profiles = [
            DeviceProfile(f"end-moe{i}", peak_gflops=p.peak_gflops,
                          mem_gb=mems[i], mem_bw_gbs=p.mem_bw_gbs,
                          net_gbps=p.net_gbps)
            for i, p in enumerate(FLEET_PROFILES[:2])
        ]
        return FleetServingEngine(
            model, params,
            end_profiles=profiles, cloud_profile=CLOUD,
            cloud_servers=cloud_servers,
            max_batch=max_batch, max_len=128,
            timing="modeled", max_spill=max_spill,
            force_splits=force_splits, expert_fleet=True,
            expert_prefetch_per_tick=4, preemption=False,
            quantize_kv=quant, quantize_experts=quant,
            quantize_boundary=quant,
        )

    # pin the planner's own optima (phase 3/4's pattern), probed with the
    # codecs off: the quantized run must serve the identical tier layout,
    # or the byte ratios would conflate codec gains with a split move
    splits = [lane.split for lane in build([1.0, 1.0]).lanes]

    # one drifted lane re-admits groups {2,3} mid-run: every re-admitted
    # slab crosses the cloud downlink, metered at the stored slab size
    def drive(quant):
        # budget sized in the run's own stored slab size -> both runs hold
        # the same slab COUNT and the wire ratio isolates bytes/slab
        slab = expert_slab_bytes(cfg, quantized=quant)
        mems = [2 * max(s, 1) * n_pos * cap_n * slab / 1e9 for s in splits]
        eng = build(mems, force_splits=splits, quant=quant)
        for r in _requests(n_requests, max_new_tokens, seed + 4):
            eng.submit(r)
        for _ in range(2):
            eng.step()
        gf = np.zeros(K)
        gf[2], gf[3] = 0.8, 0.2
        ef = np.zeros(E)
        mask_e = [g * Mk + j for g in (2, 3) for j in range(Mk)]
        ef[mask_e] = 1.0 / len(mask_e)
        eng.lanes[0]._group_freq, eng.lanes[0]._route_freq = gf, ef
        eng.update_device_state(0, DeviceState())
        done = eng.run()
        assert len(done) == n_requests, "quant fleet phase stalled"
        return eng

    ref = drive(quant=False)
    q = drive(quant=True)
    m_ref, m_q = ref.metrics(), q.metrics()

    up_ref = sum(l.link.bytes_up for l in ref.lanes)
    up_q = sum(l.link.bytes_up for l in q.lanes)
    up_ratio = up_q / max(up_ref, 1)
    assert 0 < up_ratio <= 0.55, f"fleet boundary bytes ratio {up_ratio}"
    # expert slab wire (cloud downlink), priced by the registry at the
    # stored slab size on the SAME re-admit trace
    assert m_ref["expert_bytes_down"] > 0 and m_q["expert_bytes_down"] > 0
    down_ratio = m_q["expert_bytes_down"] / m_ref["expert_bytes_down"]
    assert down_ratio <= 0.55, f"fleet expert wire ratio {down_ratio}"
    # per-lane paged-KV and slab capacity at the same memory budget
    for lane in q.lanes:
        kv = lane.kv_metrics()
        assert kv["kv_capacity_ratio"] >= 1.9, kv["kv_capacity_ratio"]
        em = lane.metrics()
        assert em["expert_capacity_ratio"] >= 1.9, em["expert_capacity_ratio"]
    for lane in ref.lanes:
        assert lane.kv_metrics()["kv_capacity_ratio"] == 1.0

    row = {
        "splits": splits,
        "boundary_bytes_up": up_q,
        "boundary_bytes_up_f32path": up_ref,
        "boundary_bytes_ratio": round(up_ratio, 4),
        "expert_bytes_down": m_q["expert_bytes_down"],
        "expert_bytes_down_f32path": m_ref["expert_bytes_down"],
        "expert_bytes_ratio": round(down_ratio, 4),
        "kv_capacity_ratio": round(
            min(l.kv_metrics()["kv_capacity_ratio"] for l in q.lanes), 4
        ),
        "aggregate_tokens_per_s": round(m_q["aggregate_tokens_per_s"], 2),
    }
    print(
        f"[fleet_throughput] quantized streams: boundary "
        f"x{row['boundary_bytes_ratio']} "
        f"({up_q/1024:.0f}KiB vs {up_ref/1024:.0f}KiB), expert wire "
        f"x{row['expert_bytes_ratio']} "
        f"({row['expert_bytes_down']/1024:.0f}KiB vs "
        f"{row['expert_bytes_down_f32path']/1024:.0f}KiB), "
        f"kv capacity x{row['kv_capacity_ratio']}, "
        f"agg={row['aggregate_tokens_per_s']:.1f} tok/s (all requests done)",
        flush=True,
    )
    return row


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--n-requests", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    row = run(n_requests=args.n_requests, max_new_tokens=args.new_tokens)
    json.dump([row], open(args.out, "w"), indent=1)
    # stable machine-readable artifact name for CI collection, regardless
    # of --out
    if args.out != "BENCH_fleet.json":
        json.dump([row], open("BENCH_fleet.json", "w"), indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
