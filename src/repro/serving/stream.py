"""Streaming end-cloud decode engine (tentpole of the PO-ECC reproduction).

``EndCloudServingEngine`` is the continuous-batching ``ServingEngine``
re-expressed as a *two-tier token pipeline*: each decode step is split at
the route-aware plan's block boundary (eq. 9-11) — blocks ``[0, split)`` and
the embedding run on the end tier (with the hardware-aware expert mask,
eq. 2-4), the boundary activation is low-rank compressed (eq. 8) and metered
through ``LinkStats``, and blocks ``[split, R)`` plus the LM head run on the
cloud tier.

**Paged KV.**  Each tier owns a shared :class:`~repro.models.kvcache.PagePool`
of fixed-size KV pages; every slot holds a bounded page table (ring
semantics at page granularity), so memory scales with the tokens actually
cached, not ``max_batch × max_len``.  The pools' host-side allocators run
between ticks; the jitted stage functions take the device page table as a
runtime argument, so there is exactly one compiled decode trace per group
shape and one prefill trace per chunk shape — never one per prompt length.
In a fleet, lanes keep private end pools while sharing one cloud pool
(fleet-wide cloud-memory admission).

**Chunked prefill.**  Admission is a pipeline stage, not a stop-the-world
event: an admitted prompt is cut into fixed-size chunks that stream through
the same end -> link -> cloud stage functions and ``StageTimeline``
resources as decode, one chunk per engine tick, writing straight into the
slot's pages (no install copy).  In-flight decode groups keep stepping
while a long prompt prefills; the finished request activates its slot at
the group's next drained tick.

**Pipelining.**  The decode batch is partitioned into ``n_groups``
equal-sized interleaved micro-batch groups (the batch is padded up to a
multiple of the group size so one trace serves every group), each with its
own boundary buffer (the double buffer).  A group alternates between two
phases: its end-step writes the boundary buffer, and — one engine tick
later — the cloud-step drains it and feeds the next token back.  While
group A's boundary is in flight / being decoded on the cloud, group B
occupies the end tier, so in steady state every stage is busy every tick
and the per-step time approaches ``max(t_end, t_comm, t_cloud)``
(``PipelinePlan.est_step_time_s``) instead of the serial sum.  Stage
compute times are *measured* on this host, link times are modeled from the
metered bytes and the (possibly drifting) bandwidth, and the overlap is
accounted by ``StageTimeline`` — the same resource-occupancy model as
``sim.simulator``, so the schedule is exactly what a two-host deployment
would realize with these stage times.

**Paged expert weights.**  For MoE models the end tier no longer holds the
full ``[E, d, f]`` expert stacks: expert weights live in a fixed-capacity
pool of per-layer slabs (:class:`~repro.core.expertpool.ExpertSlabPool`,
the expert analogue of the KV ``PagePool``), the eq. 2-4 mask is the
*target set*, and a route-frequency/LRU policy decides which experts are
resident.  The jitted end stages take the target mask and the per-layer
resident tables as *runtime* arguments and route through
``core.moe.moe_resident`` (effective mask = ``target AND resident``,
computed in-trace), so residency changes never retrace; expert compute
and HBM traffic scale with residents, not ``E``.  Slab prefetches are
booked on the same ``StageTimeline`` link resource as boundary traffic —
overlapped with decode ticks — and the swapped-in tables/mask apply only
at replan safe points, so greedy tokens stay bit-identical across the
transfer window; evictions (budget shrinks, mask changes) free slabs that
no applied table references.  Group priority for the eq. 4 greedy admit
comes from *measured* stage-1 gate statistics
(``selection.group_priority_from_freq`` over an EMA of ``group_frac``),
not natural order.

**SLO-aware admission and preemption.**  Requests carry a priority class
(``Request.priority``, 0 = interactive) and optional TTFT/TPOT SLO
targets.  Admission scans the queue in (priority, submission-seq) order —
a stable sort, so equal-priority traffic keeps FIFO fairness while a
page-hungry low-priority head can no longer starve interactive requests —
and the order head blocks its order (``SlotEngineBase._admission_order``).
When the head outranks running work and still cannot be admitted,
preemption evicts the youngest strictly-lower-priority victim at the
drained safe point (every group "ready", the same point replans apply):
an in-flight prefill job is simply cancelled and re-queued, a decoding
slot has its mapped KV pages spilled off both tier pools via the page
tables (``PagePool.spill_slot``) and restored byte-exact on re-admission
(``restore_slot``) — the resumed token stream is bit-identical to an
uninterrupted run, even across a replan in between, because the spill is
stored merged across tiers and re-split at the restore-time boundary.
Handing the engine a ``VirtualClock`` stamps request lifecycle times
(submit / first token / finish) on the modeled ``StageTimeline`` axis, so
the load harness (``serving.loadgen``) measures TTFT/TPOT on the same
deterministic clock the schedule is computed on.

**Replanning.**  Link measurements arrive through ``observe_bandwidth``
and device drift through ``update_device_state``, which also re-derives the
end tier's expert mask from the new state vector (eq. 2-4).  Either trigger
re-runs the split search against measured conditions
(``core.pipeline.replan_pipeline``).  A changed plan or mask is applied at
the next safe point — all boundary buffers drained, both tiers at equal
``lengths`` — by re-splitting params at the new block boundary and moving
the affected blocks' *pages* between the tier pools
(``kvcache.resplit_paged_blocks``: a table-aware row permutation, since the
two pools may map the same (slot, entry) set at different physical rows),
then rebuilding the stage functions.  In-flight generations continue
bit-exactly across a pure re-split (the page move is a relayout; a mask
change intentionally alters routing).  The engine defragments its private
pools at the same safe point.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp
from repro.core import expertpool
from repro.core.hardware import DeviceProfile, DeviceState, capability
from repro.core.pipeline import (
    BandwidthEstimator,
    PipelinePlan,
    plan_pipeline_split,
    plan_spec_k,
    replan_pipeline,
)
from repro.core.selection import group_priority_from_freq, validate_expert_mask
from repro.models import attention as attn_mod
from repro.models import kvcache, transformer
from repro.models.kvcache import PagePool
from repro.models.model import Model
from repro.serving.common import (
    LinkStats,
    Request,
    SlotEngineBase,
    StageTimeline,
    TraceCounter,
    VirtualClock,
    element_bytes,
    install_gc_spans,
    payload_block_until_ready,
    span,
)
from repro.serving.endcloud import (
    TierPlan,
    end_mask_from_state,
    init_tier_pages,
    plan_tiers,
    split_block_params,
    strip_expert_weights,
)
from repro.serving.faults import HealthMonitor
from repro.serving.specdecode import (
    SpecState,
    batched_accept,
    min_pow2_le,
    rollback_entries,
)

__all__ = ["EndCloudServingEngine"]

_KEEP = object()  # sentinel: "no pending mask change"


def _boundary_codec(codec, compress: bool, quantize: bool, act):
    """The boundary codec's trace-time steps, each under the ``codec`` name
    scope: ``encode`` on the end tier (the low-rank encode when one is
    configured, then the int8 quantization, which makes the payload an
    ``(codes int8, scale f16)`` tuple — the tuple-aware metering/blocking
    helpers in ``serving.common`` handle it); ``unwire`` (dequantize) and
    ``decode`` (the low-rank decode, cast to the activation dtype) on the
    cloud tier."""

    def encode(x):
        with jax.named_scope("codec"):
            z = comp.encode_1d(codec, x) if compress else x
            return comp.quantize_boundary(z) if quantize else z

    def unwire(z):
        with jax.named_scope("codec"):
            return comp.dequantize_boundary(*z, dtype=act) if quantize else z

    def decode(z):
        with jax.named_scope("codec"):
            x = comp.decode_1d(codec, z) if compress else z
            return x.astype(act)

    return encode, unwire, decode


def _greedy_ids(logits: jax.Array) -> jax.Array:
    """Greedy token ids, resolved in-trace under the ``lm_head`` scope."""
    with jax.named_scope("lm_head"):
        return jnp.argmax(logits, -1).astype(jnp.int32)


def _masks_equal(a, b) -> bool:
    if a is None or b is None:
        return a is b
    return bool(jnp.array_equal(a, b))


class _PrefillJob:
    """An admitted request streaming its prompt through the pipeline in
    chunks.  The slot is reserved (pages and all) but not active until the
    final chunk lands and the group reaches a drained tick."""

    __slots__ = (
        "req", "slot", "group", "pos", "first_tok", "first_tok_dev", "ready_s",
    )

    def __init__(self, req: Request, slot: int, group: int):
        self.req = req
        self.slot = slot
        self.group = group
        self.pos = 0  # prompt tokens prefilled so far
        self.first_tok: Optional[int] = None  # set by the final chunk
        self.first_tok_dev = None  # device scalar, resolved per-tick batched
        self.ready_s = 0.0  # modeled completion time of the last chunk


class _SpillState:
    """A preempted request's KV state, lifted off the device pools.

    ``blocks`` holds the slot's mapped page rows for ALL block repeats,
    merged across the two tiers in block order ([0, R)): restore re-splits
    at the *restore-time* split, so a replan between spill and restore (the
    page layout, even the tier boundary, may have moved) cannot corrupt the
    stream — ring-entry indices are placement-invariant, and attention
    reads pages through the rebuilt table in entry order."""

    __slots__ = (
        "entries", "blocks", "length", "next_token", "n_pages", "migrated",
    )

    def __init__(self, entries: np.ndarray, blocks: Dict, length: int,
                 next_token: int, n_pages: int):
        self.entries = entries  # mapped ring entries (same for both tiers)
        self.blocks = blocks  # pytree of [R_total, n_entries, ps, KV*hd]
        self.length = length  # _slot_len at the safe point
        self.next_token = next_token  # pending token (KV not yet written)
        self.n_pages = n_pages  # original worst-case reservation
        self.migrated = False  # lane-death migration vs in-lane preemption

    @property
    def nbytes(self) -> int:
        """Spill payload size at the *stored* representation: a quantized
        pool's leaves are the int8 codes plus their scale sidecars, so
        spill/migration byte metering sees the quantized size — spilling
        never silently re-inflates to the dense equivalent."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.blocks))


class EndCloudServingEngine(SlotEngineBase):
    def __init__(
        self,
        model: Model,
        params: Dict,
        *,
        end_profile: DeviceProfile,
        cloud_profile: DeviceProfile,
        end_state: Optional[DeviceState] = None,
        codec_params: Optional[Dict] = None,  # 1-D low-rank codec {"enc","dec"}
        compression_rank: int = 0,
        alpha: float = 0.5,
        selection_eps: float = 1.0,
        max_batch: int = 8,
        max_len: int = 512,
        n_groups: int = 2,
        force_split: Optional[int] = None,
        replan_threshold: float = 0.15,
        clock: Optional[Callable[[], float]] = None,
        timeline: Optional[StageTimeline] = None,
        resources: Tuple[str, str, str] = ("end", "link", "cloud"),
        cloud_share: float = 1.0,
        timing: str = "measured",
        page_size: int = 16,
        kv_pages: Optional[int] = None,
        prefill_chunk: int = 16,
        cloud_pool: Optional[PagePool] = None,  # fleet-shared cloud pages
        expert_pool: Optional[bool] = None,  # None = auto (on for MoE models)
        expert_slabs: Optional[int] = None,  # physical slab-pool size
        expert_resident_slots: Optional[int] = None,  # per-layer slot count
        expert_mem_frac: float = 0.5,  # end mem budget share for slabs
        expert_prefetch_per_tick: int = 2,
        expert_registry=None,  # fleet-shared expertpool.FleetExpertRegistry
        admission: str = "priority",  # "priority" | "fifo" (see SlotEngineBase)
        preemption: bool = True,  # spill lower-priority slots for a blocked head
        quantize_kv: bool = False,  # int8 KV pages + f16 per-token scale sidecars
        quantize_experts: bool = False,  # int8 slab store + per-column scales
        quantize_boundary: bool = False,  # int8 boundary payload + f16 row scales
        health: Optional[HealthMonitor] = None,  # shared retry/backoff policy
        blackout_gbps: Optional[float] = None,  # None = 5% of nominal uplink
        spec_k: int = 1,  # speculative draft-length budget (1 = off)
        link_rtt_s: float = 0.0,  # per-transfer round-trip latency (modeled)
    ):
        if not kvcache.pattern_is_pageable(model.cfg):
            raise NotImplementedError(
                "the streaming end-cloud engine serves attention-only layer "
                "patterns (paged KV + chunked prefill); SSM / cross-attention "
                "patterns are served by the dense single-tier ServingEngine"
            )
        # Equal-sized micro-batch groups: pad the slot count up to a
        # multiple of the group size so one decode trace serves every group
        # (np.linspace remainders used to compile one trace per distinct
        # group size).  Padding slots are never admitted.
        self.n_groups = max(1, min(n_groups, max_batch))
        self._group_size = -(-max_batch // self.n_groups)  # ceil
        padded_batch = self.padded_batch(max_batch, n_groups)
        super().__init__(padded_batch, clock, max_len=max_len,
                         admission=admission)
        self.request_capacity = max_batch  # user-visible slot capacity
        # Preemption only acts under priority admission (the FIFO mode is
        # the pure pre-SLO ablation: nothing jumps, nothing is evicted).
        self.preemption = preemption and admission == "priority"
        self._spilled: Dict[int, _SpillState] = {}  # request_id -> spilled KV
        self.n_preemptions = 0
        self.n_preempt_restores = 0
        self.preempt_spill_bytes = 0
        # a VirtualClock switches request stamps onto the modeled timeline
        self._virtual_time = isinstance(self.clock, VirtualClock)
        install_gc_spans()
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.end_profile = end_profile
        self.cloud_profile = cloud_profile
        self.end_state = end_state or DeviceState()
        self.selection_eps = selection_eps
        self.replan_threshold = replan_threshold
        # int8 second-stage codecs (all off by default: the dense path stays
        # the exact oracle; each flag quantizes one byte stream — KV pages,
        # the expert slab store, the pipeline-boundary payload)
        self.quantize_kv = bool(quantize_kv)
        self.quantize_experts = bool(quantize_experts)
        self.quantize_boundary = bool(quantize_boundary)

        # paged expert weights: pooled by default for MoE models — the mask
        # derivation below already reads the measured-frequency group
        # priority, so these attrs must exist before plan_tiers
        self._moe_pos = [
            i for i, spec in enumerate(model.cfg.layer_pattern) if spec.moe
        ]
        self._expert_pooled = bool(
            (expert_pool if expert_pool is not None else True)
            and model.cfg.moe is not None
            and self._moe_pos
        )
        self._route_freq: Optional[np.ndarray] = None  # [E] EMA expert_frac
        self._group_freq: Optional[np.ndarray] = None  # [K] EMA group_frac
        self._freq_decay = 0.9
        # fleet-shared expert registry: residency planning is delegated to
        # it once this lane registers (after the pool exists); the mask
        # derivation below may run before that, so both attrs exist now
        self.expert_registry = expert_registry if self._expert_pooled else None
        self._registry_lane: Optional[int] = None
        # any MoE end tier (pooled or dense-mask) measures routing stats
        self._route_stats_enabled = model.cfg.moe is not None and bool(
            self._moe_pos
        )

        self.tiers: TierPlan = plan_tiers(
            model,
            end_profile=end_profile,
            cloud_profile=cloud_profile,
            end_state=self.end_state,
            end_mask=self._derive_end_mask(self.end_state),
            codec_params=codec_params,
            compression_rank=compression_rank,
            alpha=alpha,
            selection_eps=selection_eps,
            force_split=force_split,
            cloud_share=cloud_share,
        )
        self.end_params, self.cloud_params = split_block_params(params, self.split)
        if self._expert_pooled:
            self.end_params = strip_expert_weights(self.end_params, self.cfg)

        self.link = LinkStats()
        self.bw = BandwidthEstimator(self.tiers.end_cap.net_gbps)
        # -- fault tolerance: transfer retries, link-blackout degradation --
        # (the fleet shares one HealthMonitor across lanes; standalone
        # engines get their own with the default policy)
        self.health = health or HealthMonitor()
        # below this measured rate the link is *blacked out*: the planner's
        # comm estimates stop being meaningful and the lane degrades to a
        # cloud-only plan at the next safe point (see _update_link_health)
        self.blackout_gbps = (
            blackout_gbps if blackout_gbps is not None
            else 0.05 * self.tiers.end_cap.net_gbps
        )
        self.link_degraded = False
        self._blackout_since = 0.0
        self.link_blackout_s = 0.0  # closed windows; see blackout_seconds()
        self.degraded_ticks = 0
        self.transfer_retries = 0
        self._transfer_faults = 0  # injected boundary-transfer failures
        self.n_migration_restores = 0
        # ``timeline``/``resources`` let a fleet share one occupancy clock:
        # each device brings its own end/link resources while every device's
        # cloud stage queues on one shared (possibly multi-server) resource.
        self._res_end, self._res_link, self._res_cloud = resources
        if timeline is None:
            timeline = StageTimeline(resources)
        else:
            for r in resources:
                timeline.add_resource(r)
        self.timeline = timeline
        # ``timing="measured"`` (default) feeds the timeline this host's
        # wall-clock stage times; ``"modeled"`` substitutes the planner's
        # capability cost model (gflops / device budget) — tokens are still
        # computed for real, but the schedule is deterministic and honors
        # the *declared* device speeds, which one host cannot reproduce.
        # Heterogeneous-fleet benchmarks use "modeled".
        if timing not in ("measured", "modeled"):
            raise ValueError(f"timing={timing!r}")
        self.timing = timing
        self._cloud_share = cloud_share
        self.replan_events: List[Dict] = []
        self._pending_plan: Optional[PipelinePlan] = None
        self._pending_mask = _KEEP

        # -- paged KV: one pool per tier, storage split by block range ------
        self.page_size = page_size
        self.pages_per_slot, ring = kvcache.page_geometry(
            self.cfg, max_len, page_size, chunk_headroom=prefill_chunk
        )
        if prefill_chunk > ring:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} exceeds the ring capacity "
                f"{ring} (a chunk must fit the slot's page list)"
            )
        self.prefill_chunk = prefill_chunk
        dense_pages = padded_batch * self.pages_per_slot
        self.end_pool = PagePool(
            kv_pages or dense_pages, page_size, self.pages_per_slot,
            n_slots=padded_batch,
        )
        if cloud_pool is None:
            self.cloud_pool = PagePool(
                kv_pages or dense_pages, page_size, self.pages_per_slot,
                n_slots=padded_batch,
            )
            self._cloud_base = 0
            self._cloud_shared = False
        else:
            if cloud_pool.page_size != page_size or (
                cloud_pool.pages_per_slot != self.pages_per_slot
            ):
                raise ValueError("shared cloud pool geometry mismatch")
            self.cloud_pool = cloud_pool
            self._cloud_base = cloud_pool.add_slots(padded_batch)
            self._cloud_shared = True
        dtype = jnp.dtype(self.cfg.dtype)
        self._end_pages, self._cloud_pages = init_tier_pages(
            self.cfg, self.split,
            self.end_pool.num_pages, self.cloud_pool.num_pages,
            page_size, dtype, quantized=self.quantize_kv,
        )
        self._slot_len = np.zeros((padded_batch,), np.int64)
        self._jobs: Dict[int, _PrefillJob] = {}  # slot -> in-flight prefill

        # Micro-batch groups: interleaved slot ranges, one boundary buffer
        # (the double buffer) per group.
        gsz = self._group_size
        self._group_slices = [
            (g * gsz, (g + 1) * gsz) for g in range(self.n_groups)
        ]
        self._phase = ["ready"] * self.n_groups  # "ready" | "boundary"
        self._boundary: List[Optional[jax.Array]] = [None] * self.n_groups
        self._boundary_ready_s = [0.0] * self.n_groups  # modeled arrival time
        self._group_ready_s = [0.0] * self.n_groups  # modeled token-ready time
        # Decode-only mirror of the occupancy clock: the shared timeline
        # carries decode AND prefill chunks (the honest schedule, what fleet
        # contention and makespan see), while the pipelined-vs-serial decode
        # metric compares steady-state decode against its own serial sum —
        # interleaved prefill occupancy must not pollute that ratio.
        self._metric_clock = StageTimeline(("end", "link", "cloud"))
        self._m_boundary_ready = [0.0] * self.n_groups
        self._m_group_ready = [0.0] * self.n_groups

        # -- paged expert weights: slab pool + device store/tables ----------
        self.expert_pool: Optional[expertpool.ExpertSlabPool] = None
        if self._expert_pooled:
            m = self.cfg.moe
            E = m.num_experts
            s_cap = expert_resident_slots or max(
                1, int(np.floor(m.local_selection_cap * E))
            )
            self._s_cap = min(s_cap, E)
            n_layers = len(self._moe_pos) * self.cfg.block_repeat
            # wire costs, capacity, and metering are all priced at the
            # *stored* slab size — int8 slabs are cheaper to fetch and more
            # of them fit the same memory budget; the dense size survives
            # only as the `_dense` metric baselines
            self._slab_bytes = expertpool.expert_slab_bytes(
                self.cfg, quantized=self.quantize_experts
            )
            self._slab_bytes_dense = expertpool.expert_slab_bytes(self.cfg)
            self._expert_mem_frac = expert_mem_frac
            n_slabs = expert_slabs or n_layers * self._s_cap
            self.expert_pool = expertpool.ExpertSlabPool(
                n_slabs, n_layers, E, self._s_cap
            )
            self._slab_store = expertpool.init_slab_store(
                self.cfg, n_slabs, quantized=self.quantize_experts
            )
            self._expert_prefetch_per_tick = max(1, expert_prefetch_per_tick)
            self._prefetch_queue: List[Tuple[int, int]] = []
            self._expert_ready_s = 0.0  # link-resource cursor for transfers
            self.expert_bytes_down = 0  # runtime slab prefetch traffic (cloud)
            self.expert_bytes_peer = 0  # slab traffic served by peer lanes
            self.expert_bytes_up = 0  # (evictions are drops; cloud keeps all)
            self.n_expert_prefetches = 0
            self.n_expert_peer_fetches = 0
            self.n_expert_evictions = 0
            self.expert_routed_tokens = 0  # decoded tokens through the pool
            self.expert_wire_s = 0.0  # slab wire time booked on own link
            self._expert_dirty = False
            self._applied_target = np.asarray(self.tiers.end_mask, bool)
            if self.expert_registry is not None:
                self._registry_lane = self.expert_registry.register_lane(
                    self.expert_pool,
                    link_gbps=lambda: self.bw.gbps,
                    book_link=lambda ready_s, t: self.timeline.occupy(
                        self._res_link, ready_s, t
                    ),
                )
            # initial residency ships with the deployment: filled instantly,
            # not metered — only *runtime* residency changes ride the link
            self._expert_sync(instant_lids=set(self._active_lids()))

        # -- speculative decode: draft caches, acceptance state, plan-k -----
        # ``spec_k`` is the draft-length BUDGET; the planner (plan_spec_k)
        # picks the effective k from measured bandwidth/RTT/stage times and
        # returns 1 in the compute-bound regime — k=1 means no speculative
        # machinery runs at all (no draft cache, no draft prefill, the
        # plain decode path is byte-for-byte the non-speculative engine).
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_k_max = min(int(spec_k), self.prefill_chunk)
        self.link_rtt_s = float(link_rtt_s)
        self._spec_state: Optional[SpecState] = None
        self._spec_plan_k = 1
        self._spec_fns: Dict[int, Tuple] = {}  # k -> (draft, end, cloud) fns
        self._spec_prefill = None  # jitted draft-cache prefill ([1, max_len])
        # per-group dense draft caches (blocks pytree, leaves
        # [R, gsz, W, KV, hd]); per-slot host lengths + readiness
        self._draft_cache: List[Optional[Dict]] = [None] * self.n_groups
        self._draft_len = np.zeros((padded_batch,), np.int64)
        self._draft_ready = np.zeros((padded_batch,), bool)
        # in-flight speculative round per group (set by the spec end stage,
        # consumed at the cloud drain; aborts roll provisional pages back)
        self._spec_pending: List[Optional[Dict]] = [None] * self.n_groups
        self.n_host_syncs = 0  # device->host transfers (batched per tick)

        self.n_stage_steps = 0  # decode end-steps (== drained cloud-steps)
        self.n_prefill_chunks = 0
        # This engine's own stage seconds (the timeline's busy_s would mix in
        # other lanes' cloud time when the cloud resource is fleet-shared).
        self._stage_busy = {"end": 0.0, "link": 0.0, "cloud": 0.0}
        self._prefill_busy = {"end": 0.0, "link": 0.0, "cloud": 0.0}
        self._traces: Dict[str, set] = {}
        self._build_gen = 0
        self._build_stage_fns()

    @staticmethod
    def padded_batch(max_batch: int, n_groups: int) -> int:
        """Slot count after rounding up to equal-sized micro-batch groups
        (the authoritative grouping rule; the fleet sizes its shared cloud
        pool with it)."""
        g = max(1, min(n_groups, max_batch))
        return -(-max_batch // g) * g

    # -- the active plan lives on self.tiers; everything else delegates ------

    def _derive_end_mask(self, end_state: DeviceState):
        """Hardware-aware expert mask for this end device (eq. 2-4).  One
        derivation shared by initial tier planning and replan-time state
        updates; the fleet lane overrides it with the fleet-mask semantics
        (``selection.shard_masks_for_fleet``'s never-empty guarantee).
        The greedy group admit is ordered by *measured* stage-1 routing
        frequency (EMA of the gate's ``group_frac``), not natural order."""
        return end_mask_from_state(
            self.cfg, self.end_profile, end_state,
            selection_eps=self.selection_eps,
            group_priority=self._group_priority(),
        )

    def _group_priority(self):
        if self.cfg.moe is None:
            return None
        return group_priority_from_freq(
            self._group_freq, self.cfg.moe.num_groups,
            group_cost=self._group_placement_cost(),
        )

    def _group_placement_cost(self):
        """Per-group modeled fetch cost from the fleet expert registry
        (None standalone / before registration): the eq. 4 greedy admit
        then prefers groups whose experts are already fleet-resident or
        cheap to fetch — routing sees the same map request placement
        does."""
        if self.expert_registry is None or self._registry_lane is None:
            return None
        return self.expert_registry.group_fetch_costs(
            self._registry_lane, self._active_lids(), self.cfg.moe.num_groups
        )

    # -- paged expert weights (slab pool; see core.expertpool) ----------------

    def _active_lids(self) -> List[int]:
        """Pool layer ids of the end tier's MoE layers at the current
        split: block ``b`` of pattern position ``self._moe_pos[pi]`` is
        layer ``pi * block_repeat + b``."""
        R = self.cfg.block_repeat
        return [
            pi * R + b
            for pi in range(len(self._moe_pos))
            for b in range(self.split)
        ]

    def _expert_capacity(self) -> int:
        """Slab budget from the end capability's memory term (eq. 3):
        ``expert_mem_frac`` of the budget buys slabs — a shrinking memory
        budget now actually sheds experts (evictions at the next safe
        point) instead of only suppressing routing.  Floor: every active
        end layer keeps at least one resident."""
        budget = self.tiers.end_cap.mem_budget_gb * 1e9 * self._expert_mem_frac
        n = int(budget // self._slab_bytes)
        floor_n = max(1, len(self._active_lids()))
        return max(floor_n, min(n, self.expert_pool.num_slabs))

    def _target_mask_np(self) -> np.ndarray:
        return np.asarray(self.tiers.end_mask, bool)

    def _write_slabs(self, assignments: List[Tuple[int, int, int, int]]):
        """(slab, pos_index, block, expert) -> copy weights into the store
        (one batched scatter per MoE pattern position)."""
        by_pos: Dict[int, List[Tuple[int, int, int]]] = {}
        for slab, pi, b, e in assignments:
            by_pos.setdefault(pi, []).append((slab, b, e))
        for pi, asg in by_pos.items():
            full = self.params["blocks"][f"pos{self._moe_pos[pi]}"]["moe"]
            self._slab_store = expertpool.write_slabs(
                self._slab_store, full, asg
            )

    def _lid_to_pos_block(self, lid: int) -> Tuple[int, int]:
        R = self.cfg.block_repeat
        return lid // R, lid % R

    def _plan_residency(self, active, target):
        """Residency plan for this lane: through the fleet registry when
        attached (pool policy plus the fleet de-dup rule — a duplicate of
        a peer-resident expert is only fetched when this lane's measured
        traffic justifies the slab), the isolated pool policy otherwise."""
        if self.expert_registry is not None and self._registry_lane is not None:
            return self.expert_registry.plan_lane(
                self._registry_lane, active, target, self._route_freq
            )
        return self.expert_pool.plan(active, target, self._route_freq)

    def _expert_sync(self, instant_lids=()):
        """Reconcile pool residency with the current target mask / split /
        memory budget — called at replan safe points only, so the swapped
        tables and routing mask can never change mid-boundary.  Layers in
        ``instant_lids`` (initial fill, blocks entering the end tier at a
        split change — their weights move with the unmetered block
        re-split) materialize immediately; everything else joins the
        prefetch queue and rides the link timeline."""
        pool = self.expert_pool
        target = self._target_mask_np()
        active = self._active_lids()
        pool.set_capacity(self._expert_capacity())
        wanted, evictions = self._plan_residency(active, target)
        for lid, e in evictions:
            pool.evict(lid, e)
            self.n_expert_evictions += 1
        instant_lids = set(instant_lids)
        queue: List[Tuple[int, int]] = []
        writes: List[Tuple[int, int, int, int]] = []
        for lid, e in wanted:
            if lid in instant_lids and pool.can_alloc():
                slab = pool.alloc(lid, e)
                pi, b = self._lid_to_pos_block(lid)
                writes.append((slab, pi, b, e))
            else:
                queue.append((lid, e))
        self._write_slabs(writes)
        self._prefetch_queue = queue
        self._applied_target = target
        self._expert_tables = self._build_expert_tables()
        self._emask_dev = jnp.asarray(target)
        pool.touch(active, target)
        self._expert_dirty = False

    def _build_expert_tables(self) -> Dict[str, Dict[str, jax.Array]]:
        R = self.cfg.block_repeat
        tabs = {}
        for pi, pos in enumerate(self._moe_pos):
            lids = [pi * R + b for b in range(self.split)]
            tabs[f"pos{pos}"] = expertpool.device_resident_tables(
                self.expert_pool, lids, self._s_cap
            )
        return tabs

    def _eres(self) -> Dict:
        """The pooled end stages' runtime operand: slab store + per-layer
        resident tables (shapes depend only on the split and the static
        resident-slot count, so residency changes never retrace)."""
        return {"store": self._slab_store, "tables": self._expert_tables}

    def _advance_expert_prefetch(self):
        """Transfer up to ``expert_prefetch_per_tick`` queued slabs: write
        the weights into the store (unreferenced by any applied table until
        the next safe point, so in-flight decode is untouched) and book the
        wire time on the shared link resource — prefetch overlaps decode
        on the occupancy timeline exactly like boundary traffic.  A queue
        head blocked on capacity waits for safe-point evictions."""
        if not self._expert_pooled or not self._prefetch_queue:
            return
        pool = self.expert_pool
        n = 0
        i = 0
        writes: List[Tuple[int, int, int, int]] = []
        while i < len(self._prefetch_queue) and n < self._expert_prefetch_per_tick:
            lid, e = self._prefetch_queue[i]
            if pool.table[lid, e] >= 0:
                self._prefetch_queue.pop(i)
                continue
            if not pool.can_alloc():
                break  # global budget: nothing can transfer this tick
            if pool.resident_count(lid) >= pool.max_per_layer:
                # this layer's slots wait for safe-point evictions — skip
                # it, other layers' transfers must not head-of-line block
                i += 1
                continue
            self._prefetch_queue.pop(i)
            slab = pool.alloc(lid, e)
            pi, b = self._lid_to_pos_block(lid)
            writes.append((slab, pi, b, e))
            # source pick happens at *transfer* time against the live fleet
            # map: a peer lane holding the slab serves it over the modeled
            # end<->end link when strictly cheaper than the cloud path (a
            # peer that evicted since planning falls back to the cloud)
            src = None
            if self.expert_registry is not None and (
                self._registry_lane is not None
            ):
                src, t_wire = self.expert_registry.pick_source(
                    self._registry_lane, lid, e
                )
            if src is not None and self.expert_registry.take_peer_fault():
                # injected peer-fetch failure: back off once, then re-source
                # from the cloud — the authoritative store, never the flaky
                # peer again for this slab
                self.transfer_retries += 1
                self._expert_ready_s += self.health.backoff_s(0)
                src = None
            if src is None:
                t_wire = self.link.transfer_time(self._slab_bytes, self.bw.gbps)
                self.expert_bytes_down += self._slab_bytes
            else:
                # both ends of the peer transfer ride the fleet timeline:
                # this lane's link here, the source lane's via the registry
                self.expert_registry.book_peer(
                    src, self._registry_lane, self._expert_ready_s, t_wire
                )
                self.link.record_peer(self._slab_bytes, t_wire)
                self.expert_bytes_peer += self._slab_bytes
                self.n_expert_peer_fetches += 1
            self._expert_ready_s = self.timeline.occupy(
                self._res_link, self._expert_ready_s, t_wire
            )
            self.expert_wire_s += t_wire
            self.n_expert_prefetches += 1
            self._expert_dirty = True  # tables swap at the next safe point
            n += 1
        self._write_slabs(writes)

    def _observe_route_stats(self, stats: Dict):
        """EMA the gate's measured routing statistics (summed over the end
        tier's MoE layers by the stack) — they order the eq. 4 group admit
        and the pool's prefetch/evict priorities."""
        n_layers = max(len(self._active_lids()), 1)
        with span("sync"):
            ef = np.asarray(stats["expert_frac"], np.float64) / n_layers
            gf = np.asarray(stats["group_frac"], np.float64) / n_layers
        if not (np.isfinite(ef).all() and np.isfinite(gf).all()):
            return
        d = self._freq_decay
        if self._route_freq is None:
            self._route_freq, self._group_freq = ef, gf
        else:
            self._route_freq = d * self._route_freq + (1 - d) * ef
            self._group_freq = d * self._group_freq + (1 - d) * gf

    @property
    def plan(self) -> PipelinePlan:
        return self.tiers.plan

    @property
    def split(self) -> int:
        return self.tiers.plan.split_layer

    def _cslot(self, slot: int) -> int:
        """A slot's row in the (possibly fleet-shared) cloud pool."""
        return self._cloud_base + slot

    # -- stage functions (rebuilt on every replan so the captured split /
    # -- codec flags can never go stale in a cached trace) --------------------

    def _build_stage_fns(self):
        cfg = self.cfg
        topo = self.model.topo
        tiers = self.tiers
        end_mask = tiers.end_mask
        ps = self.page_size
        pooled = self._expert_pooled
        wire_encode, wire_decode, codec_decode = _boundary_codec(
            tiers.codec, tiers.compress, self.quantize_boundary,
            jnp.dtype(cfg.dtype),
        )

        def decode_angles(lengths, B):
            pos = lengths[:, None]
            if cfg.mrope_sections is not None:
                pos = jnp.broadcast_to(pos[:, None], (B, 3, 1))
            return attn_mod.rope_angles(
                pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )

        def chunk_angles(positions):
            pos = positions
            if cfg.mrope_sections is not None:
                B, C = positions.shape
                pos = jnp.broadcast_to(pos[:, None], (B, 3, C))
            return attn_mod.rope_angles(
                pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )

        def end_step(end_params, tokens, pages, table, lengths):
            angles = decode_angles(lengths, tokens.shape[0])
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages, aux = transformer.apply_stack_decode(
                end_params, x, cfg, topo, angles, pages, lengths,
                expert_mask=end_mask, page_table=table, page_size=ps,
            )
            z = wire_encode(x)
            if self._route_stats_enabled:
                # dense-mask MoE engines measure routing too: the eq. 4
                # group priority must come from traffic, not natural order
                stats = {
                    "expert_frac": aux["expert_frac"],
                    "group_frac": aux["group_frac"],
                }
                return z, new_pages, stats
            return z, new_pages

        # pooled variants: the target mask and the resident tables/store
        # are RUNTIME operands (residency changes never retrace); the gate's
        # measured routing stats come back for the frequency EMA
        def end_step_pooled(end_params, tokens, pages, table, lengths,
                            emask, eres):
            angles = decode_angles(lengths, tokens.shape[0])
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages, aux = transformer.apply_stack_decode(
                end_params, x, cfg, topo, angles, pages, lengths,
                expert_mask=emask, page_table=table, page_size=ps,
                expert_resident=eres,
            )
            z = wire_encode(x)
            stats = {
                "expert_frac": aux["expert_frac"],
                "group_frac": aux["group_frac"],
            }
            return z, new_pages, stats

        def cloud_logits(cloud_params, z, pages, table, lengths):
            z = wire_decode(z)
            angles = decode_angles(lengths, z.shape[0])
            x = codec_decode(z)
            x, new_pages, _ = transformer.apply_stack_decode(
                cloud_params, x, cfg, topo, angles, pages, lengths,
                expert_mask=None, page_table=table, page_size=ps,
            )
            return transformer.lm_logits(cloud_params, cfg, x)[:, 0], new_pages

        def cloud_step(cloud_params, z, pages, table, lengths):
            logits, new_pages = cloud_logits(
                cloud_params, z, pages, table, lengths
            )
            # greedy ids resolved in-trace: one int32 per row crosses to the
            # host (batched per tick) instead of a [B, V] logits row
            return _greedy_ids(logits), new_pages

        def end_prefill_chunk(end_params, tokens, pages, table, start, n_valid):
            B, C = tokens.shape
            positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages = transformer.apply_stack_prefill_chunk(
                end_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=end_mask,
            )
            z = wire_encode(x)
            return z, new_pages

        def end_prefill_chunk_pooled(end_params, tokens, pages, table, start,
                                     n_valid, emask, eres):
            B, C = tokens.shape
            positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages = transformer.apply_stack_prefill_chunk(
                end_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=emask,
                expert_resident=eres,
            )
            z = wire_encode(x)
            return z, new_pages

        def cloud_prefill_hidden(cloud_params, z, pages, table, start,
                                 n_valid):
            z = wire_decode(z)
            B, C = z.shape[:2]
            positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = codec_decode(z)
            return transformer.apply_stack_prefill_chunk(
                cloud_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=None,
            )

        def cloud_prefill_logits(cloud_params, z, pages, table, start,
                                 n_valid):
            x, new_pages = cloud_prefill_hidden(
                cloud_params, z, pages, table, start, n_valid
            )
            return transformer.lm_logits(cloud_params, cfg, x), new_pages

        def cloud_prefill_chunk(cloud_params, z, pages, table, start, n_valid):
            x, new_pages = cloud_prefill_hidden(
                cloud_params, z, pages, table, start, n_valid
            )
            B = x.shape[0]
            x_last = x[jnp.arange(B), jnp.maximum(n_valid - 1, 0)][:, None]
            logits = transformer.lm_logits(cloud_params, cfg, x_last)[:, 0]
            return _greedy_ids(logits), new_pages

        self._build_gen += 1
        gen = self._build_gen

        def counted(name, fn):
            # every stage takes its tier's page storage third and returns
            # it updated; the caller rebinds the returned storage.  The
            # argument is not donated: the program copies each pool leaf
            # once on entry and writes that copy in place, and a caller may
            # still read the storage it passed
            return TraceCounter(
                jax.jit(fn), self._traces.setdefault(name, set()), gen
            )

        self._end_step = counted(
            "end_step", end_step_pooled if pooled else end_step
        )
        self._cloud_step = counted("cloud_step", cloud_step)
        self._end_prefill_chunk = counted(
            "end_prefill_chunk",
            end_prefill_chunk_pooled if pooled else end_prefill_chunk,
        )
        self._cloud_prefill_chunk = counted(
            "cloud_prefill_chunk", cloud_prefill_chunk
        )
        # the cloud stages' bodies before the in-trace argmax, un-jitted and
        # never called by the served path: logit-level checks (chip_smoke.py)
        # run the end stages above with these to compare against a reference
        self.cloud_logit_fns = {
            "cloud_step": cloud_logits,
            "cloud_prefill_chunk": cloud_prefill_logits,
        }
        # speculative stage fns close over the same codec/mask/split state:
        # drop the per-k cache so they rebuild lazily against the new plan
        self._spec_fns = {}
        self._spec_prefill = None
        self._recompute_spec_plan()
        self._warmup_stage_fns()

    def _warmup_stage_fns(self):
        """Compile the stage functions for the (single) group shape and the
        (single) chunk shape so measured stage times reflect steady-state
        compute, not tracing.  Warmup writes are routed to the garbage page
        (all-garbage table) and the returned storage is discarded."""
        gsz = self._group_size
        inactive = np.zeros((gsz,), bool)
        tokens = jnp.zeros((gsz, 1), jnp.int32)
        lengths = jnp.zeros((gsz,), jnp.int32)
        te = self.end_pool.device_rows(range(gsz), active=inactive)
        tc = self.cloud_pool.device_rows(
            [self._cslot(s) for s in range(gsz)], active=inactive
        )
        eargs = (
            (self._emask_dev, self._eres()) if self._expert_pooled else ()
        )
        z, _, *_ = self._end_step(
            self.end_params, tokens, self._end_pages, te, lengths, *eargs
        )
        ids, _ = self._cloud_step(
            self.cloud_params, z, self._cloud_pages, tc, lengths
        )
        ids.block_until_ready()

        C = self.prefill_chunk
        ctok = jnp.zeros((1, C), jnp.int32)
        start = jnp.zeros((1,), jnp.int32)
        valid = jnp.ones((1,), jnp.int32)
        te1 = self.end_pool.device_rows([0], active=np.zeros((1,), bool))
        tc1 = self.cloud_pool.device_rows(
            [self._cslot(0)], active=np.zeros((1,), bool)
        )
        z, _ = self._end_prefill_chunk(
            self.end_params, ctok, self._end_pages, te1, start, valid, *eargs
        )
        ids, _ = self._cloud_prefill_chunk(
            self.cloud_params, z, self._cloud_pages, tc1, start, valid
        )
        ids.block_until_ready()

    # -- speculative decode: draft on the end tier, verify in one C=k chunk ---
    #
    # A speculative round replaces one single-token pipeline round for a
    # group: the end tier drafts k-1 tokens with a cheap full-stack forward
    # under its expert mask (against a private dense "draft cache"), runs
    # its block range over the k-position chunk [pending, y_1..y_{k-1}],
    # ships ONE boundary payload, and the cloud verifies all k positions in
    # a single chunked step off the paged pool.  The accepted prefix
    # commits; provisional pages past the first rejection are unmapped
    # (pure table surgery — rejected tokens only ever lived in
    # lazily-mapped pages) and the verify argmax at the rejection point is
    # the corrected token, so greedy output matches non-speculative decode
    # by construction.

    def _recompute_spec_plan(self):
        """Re-run the plan-time draft-length choice against measured link
        conditions (safe points and bandwidth observations).  k=1 disables
        every piece of speculative machinery — the engine is then
        byte-for-byte the plain pipeline."""
        if self.spec_k_max <= 1:
            self._spec_plan_k = 1
            return
        acc = 0.7
        if self._spec_state is not None and self._spec_state.acceptance is not None:
            acc = self._spec_state.acceptance
        ratio = self.tiers.compression_ratio if self.tiers.compress else 1.0
        k = plan_spec_k(
            self.tiers.layer_gflops,
            self.tiers.boundary_bytes,
            self.tiers.end_cap,
            self.tiers.cloud_cap,
            split=self.split,
            link_rtt_s=self.link_rtt_s,
            measured_gbps=self.bw.gbps,
            compression_ratio=ratio,
            acceptance=acc,
            k_max=self.spec_k_max,
        )
        self._spec_plan_k = k
        if k > 1:
            if self._spec_state is None:
                self._spec_state = SpecState(k)
            else:
                st = self._spec_state
                st.k_plan = k
                st.k_eff = max(2, min(st.k_eff, min_pow2_le(k)))

    def _spec_emask(self):
        """The draft model's expert mask: the plan's target set.  The
        draft forward runs the FULL stack from ``self.params`` (all blocks
        plus embedding and head) restricted to end-resident experts — the
        cheap self-speculation draft; dense models draft exactly."""
        if self.tiers.end_mask is None:
            return None
        return jnp.asarray(self.tiers.end_mask)

    def _init_draft_cache(self) -> Dict:
        return kvcache.init_cache(
            self.cfg, self._group_size, self.max_len, jnp.dtype(self.cfg.dtype)
        )["blocks"]

    def _draft_prefill_fn(self):
        if self._spec_prefill is None:
            model, max_len = self.model, self.max_len

            def spec_draft_prefill(params, tokens, emask):
                _logits, cache = model.prefill(
                    params, {"tokens": tokens}, max_len=max_len,
                    expert_mask=emask,
                )
                return cache["blocks"]

            self._spec_prefill = TraceCounter(
                jax.jit(spec_draft_prefill),
                self._traces.setdefault("spec_draft_prefill", set()),
                self._build_gen,
            )
        return self._spec_prefill

    def _spec_fns_for_k(self, k: int):
        """Build (lazily, cached per k until the next stage rebuild) the
        three jitted speculative stage functions for chunk size k: the
        end-tier draft scan, the end-tier C=k boundary chunk, and the
        cloud C=k verify chunk returning per-position greedy ids."""
        if k in self._spec_fns:
            return self._spec_fns[k]
        cfg = self.cfg
        topo = self.model.topo
        tiers = self.tiers
        end_mask = tiers.end_mask
        ps = self.page_size
        wire_encode, wire_decode, codec_decode = _boundary_codec(
            tiers.codec, tiers.compress, self.quantize_boundary,
            jnp.dtype(cfg.dtype),
        )

        def decode_angles(lengths, B):
            pos = lengths[:, None]
            if cfg.mrope_sections is not None:
                pos = jnp.broadcast_to(pos[:, None], (B, 3, 1))
            return attn_mod.rope_angles(
                pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )

        def chunk_angles(positions):
            pos = positions
            if cfg.mrope_sections is not None:
                B, C = positions.shape
                pos = jnp.broadcast_to(pos[:, None], (B, 3, C))
            return attn_mod.rope_angles(
                pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )

        def spec_draft(params, tokens, blocks, lengths, emask):
            # k greedy steps off the dense draft cache in ONE trace.  Step
            # 0 consumes the pending token (writing its draft-KV at the
            # base position); steps 1..k-1 consume their predecessor's
            # argmax.  The k-th output is discarded — only k-1 drafts feed
            # the chunk — but its WRITE keeps the draft cache contiguous
            # through position base+k-1 for the full-accept case.
            B = tokens.shape[0]
            drafts = []
            for _ in range(k):
                angles = decode_angles(lengths, B)
                x = transformer.embed_inputs(params, cfg, tokens)
                x, blocks, _aux = transformer.apply_stack_decode(
                    params, x, cfg, topo, angles, blocks, lengths,
                    expert_mask=emask,
                )
                logits = transformer.lm_logits(params, cfg, x)[:, 0]
                tokens = _greedy_ids(logits)[:, None]
                drafts.append(tokens[:, 0])
                lengths = lengths + 1
            return jnp.stack(drafts, axis=1), blocks

        def spec_end(end_params, tokens, pages, table, start, n_valid):
            positions = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages = transformer.apply_stack_prefill_chunk(
                end_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=end_mask,
            )
            z = wire_encode(x)
            return z, new_pages

        def spec_end_pooled(end_params, tokens, pages, table, start, n_valid,
                            emask, eres):
            positions = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = transformer.embed_inputs(end_params, cfg, tokens)
            x, new_pages = transformer.apply_stack_prefill_chunk(
                end_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=emask,
                expert_resident=eres,
            )
            z = wire_encode(x)
            return z, new_pages

        def spec_cloud(cloud_params, z, pages, table, start, n_valid):
            z = wire_decode(z)
            positions = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
            angles = chunk_angles(positions)
            x = codec_decode(z)
            x, new_pages = transformer.apply_stack_prefill_chunk(
                cloud_params, x, cfg, topo, angles, pages, table,
                positions, n_valid, ps, expert_mask=None,
            )
            # per-position greedy ids, resolved in-trace: k int32 per row
            # cross back down the link, never the [B, k, V] logits
            logits = transformer.lm_logits(cloud_params, cfg, x)
            return _greedy_ids(logits), new_pages

        gen = self._build_gen

        def counted(name, fn):
            return TraceCounter(
                jax.jit(fn), self._traces.setdefault(name, set()), gen
            )

        fns = (
            counted(f"spec_draft_k{k}", spec_draft),
            counted(
                f"spec_end_k{k}",
                spec_end_pooled if self._expert_pooled else spec_end,
            ),
            counted(f"spec_cloud_k{k}", spec_cloud),
        )
        self._spec_fns[k] = fns
        self._warmup_spec_fns(k, fns)
        return fns

    def _warmup_spec_fns(self, k: int, fns):
        """Compile the spec stage functions for the group/chunk shapes
        (garbage-routed tables, discarded storage) so measured round times
        never include tracing."""
        draft_fn, end_fn, cloud_fn = fns
        gsz = self._group_size
        inactive = np.zeros((gsz,), bool)
        tokens = jnp.zeros((gsz, 1), jnp.int32)
        lengths = jnp.zeros((gsz,), jnp.int32)
        drafts, _ = draft_fn(
            self.params, tokens, self._init_draft_cache(), lengths,
            self._spec_emask(),
        )
        te = self.end_pool.device_rows(range(gsz), active=inactive)
        tc = self.cloud_pool.device_rows(
            [self._cslot(s) for s in range(gsz)], active=inactive
        )
        eargs = (
            (self._emask_dev, self._eres()) if self._expert_pooled else ()
        )
        ctok = jnp.zeros((gsz, k), jnp.int32)
        start = jnp.zeros((gsz,), jnp.int32)
        valid = jnp.ones((gsz,), jnp.int32)
        z, _ = end_fn(
            self.end_params, ctok, self._end_pages, te, start, valid, *eargs
        )
        ids, _ = cloud_fn(
            self.cloud_params, z, self._cloud_pages, tc, start, valid
        )
        ids.block_until_ready()

    def _draft_seconds(self, n_tokens: int) -> Optional[float]:
        """Modeled end-tier seconds for ``n_tokens`` through the FULL
        stack (the draft forward runs every block on the end device); None
        in measured mode."""
        if self.timing != "modeled":
            return None
        rate = self.tiers.end_cap.gflop_budget * 1e3
        return n_tokens * sum(self.tiers.layer_gflops) / max(rate, 1e-9)

    def _install_draft(self, slot: int):
        """(Re)build one slot's draft cache by prefilling its committed
        token stream through the draft model — at activation, at restore
        after preemption/migration, and when the plan turns speculation on
        mid-run.  One jitted [1, max_len] trace serves every length; the
        end tier pays the forward on the timeline like any prefill."""
        req = self.slots[slot]
        L = int(self._slot_len[slot])
        stream = list(req.prompt) + list(req.generated)
        padded = np.zeros((self.max_len,), np.int32)
        padded[:L] = np.asarray(stream[:L], np.int32)
        t0 = time.perf_counter()
        blocks = self._draft_prefill_fn()(
            self.params, jnp.asarray(padded)[None], self._spec_emask()
        )
        with span("sync"):
            jax.block_until_ready(blocks)
        td = self._draft_seconds(L)
        if td is None:
            td = time.perf_counter() - t0
        g = self._group_of(slot)
        r = slot - g * self._group_size
        if self._draft_cache[g] is None:
            self._draft_cache[g] = self._init_draft_cache()
        self._draft_cache[g] = jax.tree.map(
            lambda big, one: big.at[:, r].set(one[:, 0].astype(big.dtype)),
            self._draft_cache[g], blocks,
        )
        self._draft_len[slot] = L
        self._draft_ready[slot] = True
        done = self.timeline.occupy(self._res_end, self._group_ready_s[g], td)
        self._prefill_busy["end"] += td
        self._group_ready_s[g] = max(self._group_ready_s[g], done)

    def _spec_refresh_drafts(self):
        """Build draft caches for active slots that lack one (plan turned
        speculation on mid-run, or a restore invalidated the cache) —
        only while the slot's group is drained, so a pending round's
        commit can never clobber the fresh cache."""
        for slot in range(self.max_batch):
            if (
                self._active[slot]
                and not self._draft_ready[slot]
                and self.slots[slot] is not None
                and self._phase[self._group_of(slot)] == "ready"
            ):
                self._install_draft(slot)

    def _spec_round_k(self, g: int) -> int:
        """Draft length for this group's next round: the adaptive k while
        speculation is planned AND some active row has a fresh draft cache
        and at least two tokens of budget left; 1 (the plain path)
        otherwise."""
        if self._spec_plan_k <= 1 or self._spec_state is None:
            return 1
        gs, ge = self._group_slices[g]
        for s in range(gs, ge):
            req = self.slots[s]
            if (
                self._active[s]
                and self._draft_ready[s]
                and req is not None
                and req.max_new_tokens - len(req.generated) >= 2
            ):
                return max(2, self._spec_state.k_eff)
        return 1

    def _run_end_stage_spec(self, g: int, k: int):
        """Speculative end stage: draft scan + C=k boundary chunk.  Pages
        the chunk touches beyond the committed length are mapped
        PROVISIONALLY (``map_tokens`` returns exactly the new entries);
        the commit/rollback happens when the verify ids drain."""
        gs, ge = self._group_slices[g]
        gsz = ge - gs
        active = self._active[gs:ge]
        base_len = self._slot_len[gs:ge].copy()
        draft_fn, end_fn, _ = self._spec_fns_for_k(k)

        # per-row verified positions: full k with a fresh draft and budget,
        # the bare pending token otherwise (stale cache / budget edge);
        # inactive rows verify one garbage-routed padding position, exactly
        # like the warmup path
        n_valid = np.ones((gsz,), np.int64)
        for i, slot in enumerate(range(gs, ge)):
            req = self.slots[slot]
            if req is None or not self._active[slot]:
                continue
            if self._draft_ready[slot]:
                n_valid[i] = max(
                    1, min(k, req.max_new_tokens - len(req.generated))
                )

        # draft scan: k steps off the dense draft cache, one trace
        tokens = jnp.asarray(self._next_token[gs:ge], jnp.int32)
        dlens = jnp.asarray(self._draft_len[gs:ge], jnp.int32)
        dcache = self._draft_cache[g]
        if dcache is None:
            dcache = self._init_draft_cache()
        t0 = time.perf_counter()
        drafts_dev, dcache = draft_fn(
            self.params, tokens, dcache, dlens, self._spec_emask()
        )
        with span("sync"):
            jax.block_until_ready(drafts_dev)
        td = self._draft_seconds(gsz * k)
        if td is None:
            td = time.perf_counter() - t0
        self._draft_cache[g] = dcache

        # provisionally map the chunk's pages in both pools (lockstep)
        new_e: Dict[int, List[int]] = {}
        new_c: Dict[int, List[int]] = {}
        for i, slot in enumerate(range(gs, ge)):
            if not self._active[slot]:
                continue
            L = int(base_len[i])
            ents = self.end_pool.map_tokens(slot, L, L + int(n_valid[i]))
            ents_c = self.cloud_pool.map_tokens(
                self._cslot(slot), L, L + int(n_valid[i])
            )
            if ents != ents_c:
                raise RuntimeError(
                    f"tier pools out of lockstep for slot {slot}: "
                    f"{ents} vs {ents_c}"
                )
            new_e[slot] = ents
            new_c[slot] = ents_c

        # end-tier chunk over [pending, y_1..y_{k-1}]
        tok_chunk = jnp.concatenate([tokens, drafts_dev[:, : k - 1]], axis=1)
        table = self.end_pool.device_rows(range(gs, ge), active=active)
        start = jnp.asarray(base_len, jnp.int32)
        nv_dev = jnp.asarray(n_valid, jnp.int32)
        eargs = (
            (self._emask_dev, self._eres()) if self._expert_pooled else ()
        )
        t1 = time.perf_counter()
        z, self._end_pages = end_fn(
            self.end_params, tok_chunk, self._end_pages, table, start,
            nv_dev, *eargs,
        )
        with span("sync"):
            payload_block_until_ready(z)
        te = self._stage_seconds("end", gsz * k)
        if te is None:
            te = time.perf_counter() - t1

        # boundary metering: per-position bytes x valid positions of
        # active rows (padding rows and positions never cross the wire)
        per_pos = sum(
            int(l.dtype.itemsize * int(np.prod(l.shape[2:])))
            for l in (z if isinstance(z, tuple) else (z,))
        )
        n_tok_active = int(n_valid[active].sum())
        t_comm = self._link_transfer(per_pos * n_tok_active)
        if self._expert_pooled:
            self.expert_routed_tokens += n_tok_active

        done_e = self.timeline.occupy(
            self._res_end, self._group_ready_s[g], td + te
        )
        done_l = self.timeline.occupy(self._res_link, done_e, t_comm)
        m_e = self._metric_clock.occupy("end", self._m_group_ready[g], td + te)
        self._m_boundary_ready[g] = self._metric_clock.occupy(
            "link", m_e, t_comm
        )
        self._stage_busy["end"] += td + te
        self._stage_busy["link"] += t_comm
        self.n_stage_steps += 1

        self._boundary[g] = z
        self._boundary_ready_s[g] = done_l
        self._phase[g] = "boundary"
        self._spec_pending[g] = {
            "k": k,
            "drafts": drafts_dev,
            "base_len": base_len,
            "n_valid": n_valid,
            "new_entries_end": new_e,
            "new_entries_cloud": new_c,
        }

    def _drain_cloud_stage_spec(self, g: int) -> Dict:
        """Cloud half of a speculative round: one C=k verify chunk off the
        paged pool; per-position greedy ids come back down the link.  The
        host-side accept/commit happens in ``_harvest_drained`` so the
        draft/verify device arrays join the tick's single batched
        device->host transfer."""
        pend = self._spec_pending[g]
        gs, ge = self._group_slices[g]
        k = pend["k"]
        _, _, cloud_fn = self._spec_fns_for_k(k)
        z = self._boundary[g]
        table = self.cloud_pool.device_rows(
            [self._cslot(s) for s in range(gs, ge)],
            active=self._active[gs:ge],
        )
        start = jnp.asarray(pend["base_len"], jnp.int32)
        nv = jnp.asarray(pend["n_valid"], jnp.int32)
        t0 = time.perf_counter()
        ids_dev, self._cloud_pages = cloud_fn(
            self.cloud_params, z, self._cloud_pages, table, start, nv
        )
        with span("sync"):
            ids_dev.block_until_ready()
        tc = self._stage_seconds("cloud", (ge - gs) * k)
        if tc is None:
            tc = time.perf_counter() - t0

        done_c = self.timeline.occupy(
            self._res_cloud, self._boundary_ready_s[g], tc
        )
        self._m_group_ready[g] = self._metric_clock.occupy(
            "cloud", self._m_boundary_ready[g], tc
        )
        self._stage_busy["cloud"] += tc
        self._group_ready_s[g] = done_c
        active = self._active[gs:ge]
        n_tok_active = int(pend["n_valid"][active].sum())
        # variable-k downlink: one verify id per valid position of each
        # active row (the plain path's one id per row, scaled by k)
        self.link.record_down(n_tok_active * element_bytes(jnp.int32))

        self._boundary[g] = None
        self._phase[g] = "ready"
        self._spec_pending[g] = None
        return {
            "g": g, "kind": "spec", "done_c": done_c,
            "dev": (pend["drafts"], ids_dev), "pend": pend,
        }

    def _spec_commit(self, rec: Dict, drafts: np.ndarray,
                     verify: np.ndarray) -> int:
        """Host side of a speculative round, after the batched transfer:
        greedy accept per row, roll provisional pages past the committed
        prefix back in BOTH pools (lockstep preserved — the entry lists
        were asserted equal at map time), commit the accepted tokens, and
        feed the acceptance EMA."""
        g = rec["g"]
        pend = rec["pend"]
        gs, ge = self._group_slices[g]
        base_len = pend["base_len"]
        active = self._active[gs:ge]
        nv_eff = np.where(active, pend["n_valid"], 0)
        committed, _nrej = batched_accept(drafts, verify, nv_eff)
        emitted = 0
        n_drafted = n_accepted = 0
        rolled = False
        for i, slot in enumerate(range(gs, ge)):
            if not active[i]:
                continue
            toks = committed[i]
            n_commit = len(toks)  # >= 1: row 0's verify id always commits
            L = int(base_len[i])
            rb = rollback_entries(
                pend["new_entries_end"].get(slot, []),
                base_len=L, n_commit=n_commit,
                page_size=self.page_size,
                pages_per_slot=self.pages_per_slot,
            )
            if rb:
                self.end_pool.rollback(slot, rb)
                self.cloud_pool.rollback(self._cslot(slot), rb)
                rolled = True
            self._slot_len[slot] = L + n_commit
            if self._draft_ready[slot]:
                # the accepted prefix is, by the accept rule, exactly what
                # the draft scan wrote — the draft cache stays aligned
                self._draft_len[slot] = L + n_commit
            n_drafted += int(nv_eff[i]) - 1
            n_accepted += n_commit - 1
            emitted += self._harvest_tokens(slot, toks)
        if self._spec_state is not None:
            self._spec_state.observe_round(
                n_drafted, n_accepted,
                rolled_back=rolled or n_accepted < n_drafted,
            )
        return emitted

    def _spec_abort(self, g: int):
        """Drop an in-flight speculative round (lane death / boundary
        drop): every provisionally-mapped page unmaps, nothing commits.
        The group's slot state is untouched — still at the pre-round token
        boundary, exactly like a dropped plain boundary."""
        pend = self._spec_pending[g]
        if pend is None:
            return
        for slot, ents in pend["new_entries_end"].items():
            if ents:
                self.end_pool.rollback(slot, ents)
        for slot, ents in pend["new_entries_cloud"].items():
            if ents:
                self.cloud_pool.rollback(self._cslot(slot), ents)
        self._spec_pending[g] = None
        gs, ge = self._group_slices[g]
        self._draft_ready[gs:ge] = False
        if self._spec_state is not None:
            self._spec_state.rollbacks += 1

    # -- admission: chunked prefill as a pipeline stage -----------------------

    def _group_of(self, slot: int) -> int:
        return slot // self._group_size

    def _slot_usable(self, slot: int) -> bool:
        # padding slots (batch rounded up to equal groups) never admit;
        # slots mid-prefill are spoken for
        return slot < self.request_capacity and slot not in self._jobs

    def _pages_for(self, req: Request) -> int:
        return kvcache.pages_needed(
            len(req.prompt) + req.max_new_tokens,
            self.page_size, self.pages_per_slot,
        )

    def _page_capacity(self):
        return min(self.end_pool.num_pages, self.cloud_pool.num_pages)

    def _admit(self):
        """Admit waiting requests in ``_admission_order`` (priority class,
        then submission seq — see ``SlotEngineBase``): reserve the
        request's worst-case page count in BOTH tier pools (admission is
        page-aware — a free slot without pages stays idle), then either
        start a chunked-prefill job or, for a previously preempted request,
        restore its spilled KV and resume decode in place.  The order head
        blocks its whole order (admitting past a page-blocked head would
        keep pages occupied and starve it); when the blocked head outranks
        running work and preemption is on, a strictly lower-priority slot
        is spilled to make room and admission retries."""
        while True:
            self._admit_pass()
            if self.preemption and self._try_preempt():
                continue  # a victim was spilled: the head may now admit
            break

    def _admit_pass(self) -> int:
        admitted = 0
        free = [
            s for s in range(self.max_batch)
            if self.slots[s] is None and self._slot_usable(s)
        ]
        for req in self._admission_order():
            spilled = req.request_id in self._spilled
            # restores activate their slot immediately, which is only safe
            # while the slot's group has no boundary in flight (engine
            # ticks admit with every group drained; direct _admit calls
            # may not)
            usable = [
                s for s in free
                if not spilled or self._phase[self._group_of(s)] == "ready"
            ]
            if not usable:
                break
            need = self._pages_for(req)
            if not (
                self.end_pool.can_reserve(need)
                and self.cloud_pool.can_reserve(need)
            ):
                break
            slot = usable[0]
            free.remove(slot)
            self.waiting.remove(req)
            if spilled:
                # PagePool.restore_slot re-reserves internally
                self._restore_into_slot(slot, req)
            else:
                self.end_pool.reserve(slot, need)
                self.cloud_pool.reserve(self._cslot(slot), need)
                job = _PrefillJob(req, slot, self._group_of(slot))
                if self._virtual_time:
                    # prefill cannot start before the request arrived
                    job.ready_s = req.submit_time
                if req.admit_time is None:
                    # the modeled schedule books the prefill from its ready
                    # time, so on the virtual clock admission is that time
                    req.admit_time = (
                        job.ready_s if self._virtual_time else self.clock()
                    )
                self._jobs[slot] = job
            admitted += 1
        return admitted

    # -- preemption: spill a low-priority slot at the drained safe point ------

    def preemptible_slots(self, priority: int) -> int:
        """How many running victims a request of class ``priority`` could
        evict: active decode slots of strictly lower classes (prefill jobs
        are never preempted — see ``_try_preempt``).  Zero when preemption
        is off.  The fleet frontend adds this to a lane's admission
        capacity so a high-priority request is dispatched into a full lane
        instead of parking behind it."""
        if not self.preemption:
            return 0
        return sum(
            1 for s in range(self.max_batch)
            if self.slots[s] is not None and self.slots[s].priority > priority
        )

    def _try_preempt(self) -> bool:
        """If the admission head outranks running work and cannot be
        admitted, evict one victim — the youngest decoding slot of the
        lowest priority class strictly below the head's, its KV spilled
        via the page tables and restored intact on re-admission.  Only
        *running* (decoding) slots are victims: an in-flight prefill job
        is short and bounded, and cancelling it would discard its finished
        chunks — evicting prefill under sustained interactive pressure
        livelocks the low-priority class (it re-runs the same chunks
        forever) without buying latency.  Returns True iff a victim was
        evicted; ``_admit`` then retries, evicting further victims if one
        was not enough."""
        queue = self._admission_order()
        if not queue:
            return False
        head = queue[0]
        victims = [
            s for s in range(self.max_batch)
            if self.slots[s] is not None
            and self.slots[s].priority > head.priority
        ]
        if not victims:
            return False
        # feasibility: even evicting every candidate must cover the head's
        # page needs in both pools, else the spills are wasted churn
        need = self._pages_for(head)
        e_avail = self.end_pool.pages_available + sum(
            self.end_pool.reserved_pages(s) for s in victims
        )
        c_avail = self.cloud_pool.pages_available + sum(
            self.cloud_pool.reserved_pages(self._cslot(s)) for s in victims
        )
        if e_avail < need or c_avail < need:
            return False
        # victim choice is deterministic: lowest class, youngest arrival
        _, _, victim = max(
            (self.slots[s].priority, self.slots[s].seq, s) for s in victims
        )
        self._preempt_slot(victim)
        return True

    def _spill_slot_state(self, slot: int) -> _SpillState:
        """Spill mechanics shared by in-lane preemption and lane-death
        migration: copy the slot's mapped page rows off both tier storages
        (merged across tiers in block order — see ``_SpillState``), free
        the slot and both reservations.  Only called with the slot's group
        drained, so ``_slot_len``/``_next_token`` are at a token boundary:
        the pending token's KV is not yet written, exactly the state a
        fresh activation leaves behind.  The caller owns the request's
        re-queue and the counter bookkeeping."""
        entries_e, phys_e, n_pages = self.end_pool.spill_slot(slot)
        entries_c, phys_c, _ = self.cloud_pool.spill_slot(self._cslot(slot))
        if not np.array_equal(entries_e, entries_c):
            raise RuntimeError(
                f"tier pools out of lockstep for slot {slot}: "
                f"{entries_e.tolist()} vs {entries_c.tolist()}"
            )
        ie = jnp.asarray(phys_e, jnp.int32)
        ic = jnp.asarray(phys_c, jnp.int32)
        end_part = jax.tree.map(lambda l: np.asarray(l[:, ie]), self._end_pages)
        cloud_part = jax.tree.map(
            lambda l: np.asarray(l[:, ic]), self._cloud_pages
        )
        blocks = jax.tree.map(
            lambda a, b: np.concatenate([a, b], axis=0), end_part, cloud_part
        )
        st = _SpillState(
            entries_e, blocks, int(self._slot_len[slot]),
            int(self._next_token[slot, 0]), n_pages,
        )
        self.slots[slot] = None
        self._active[slot] = False
        self._slot_len[slot] = 0
        self._draft_ready[slot] = False
        return st

    def _preempt_slot(self, slot: int):
        """Spill a decoding slot and re-queue its request with the spilled
        KV parked under its request id for in-lane restoration."""
        req = self.slots[slot]
        st = self._spill_slot_state(slot)
        self._spilled[req.request_id] = st
        self.preempt_spill_bytes += st.nbytes
        req.n_preemptions += 1
        self.n_preemptions += 1
        self.waiting.append(req)

    def _restore_into_slot(self, slot: int, req: Request):
        """Re-admit a preempted request: both pools have re-reserved its
        original page count; map its spilled entries, scatter the saved
        page data into the new physical rows split at the *current* tier
        boundary, and resume decode mid-stream — the token stream continues
        bit-identically because page contents are byte-exact copies and
        attention reads entries, not physical rows."""
        st = self._spilled.pop(req.request_id)
        phys_e = self.end_pool.restore_slot(slot, st.entries, st.n_pages)
        phys_c = self.cloud_pool.restore_slot(
            self._cslot(slot), st.entries, st.n_pages
        )
        s = self.split
        ie = jnp.asarray(phys_e, jnp.int32)
        ic = jnp.asarray(phys_c, jnp.int32)
        self._end_pages = jax.tree.map(
            lambda l, d: l.at[:, ie].set(jnp.asarray(d[:s], l.dtype)),
            self._end_pages, st.blocks,
        )
        self._cloud_pages = jax.tree.map(
            lambda l, d: l.at[:, ic].set(jnp.asarray(d[s:], l.dtype)),
            self._cloud_pages, st.blocks,
        )
        self._slot_len[slot] = st.length
        self.slots[slot] = req
        self._next_token[slot, 0] = st.next_token
        self._active[slot] = True
        # the draft cache did not travel with the spill; rebuild it at the
        # next drained tick (_spec_refresh_drafts) if speculation is on
        self._draft_ready[slot] = False
        if st.migrated:
            self.n_migration_restores += 1
            req.n_migrations += 1
        else:
            self.n_preempt_restores += 1
        if self._virtual_time:
            # the resumed stream cannot decode before "now"
            g = self._group_of(slot)
            self._group_ready_s[g] = max(
                self._group_ready_s[g], self.clock.now
            )

    def evacuate(self) -> Tuple[List[Request], Dict[str, _SpillState], int]:
        """Lane death: spill every in-flight decode slot through the
        preemption path (KV page blocks are placement-invariant, so a
        surviving lane with a *different* split restores them bit-exactly),
        restart in-flight prefill jobs from scratch (their first token is
        never in ``generated`` before activation, so a re-run is
        exactly-once clean), and hand everything back to the fleet for
        re-placement.  In-flight boundaries are dropped — the slot state is
        still at the pre-step token boundary until the cloud stage lands,
        so the migrated lane simply recomputes the lost step.  Returns
        ``(requests in submission order, request_id -> spill state,
        spilled bytes at stored size)``."""
        for g in range(len(self._phase)):
            # an in-flight speculative round must unmap its provisional
            # pages BEFORE the spill walks the page tables — spilling them
            # would smuggle unverified KV into the migrated state
            self._spec_abort(g)
            self._boundary[g] = None
            self._phase[g] = "ready"
        spilled: Dict[str, _SpillState] = {}
        nbytes = 0
        for slot in range(self.max_batch):
            req = self.slots[slot]
            if req is None:
                continue
            st = self._spill_slot_state(slot)
            st.migrated = True
            spilled[req.request_id] = st
            nbytes += st.nbytes
            self.waiting.append(req)
        for slot in sorted(self._jobs):
            job = self._jobs.pop(slot)
            self._release_slot(slot)
            self.waiting.append(job.req)
        for rid, st in self._spilled.items():
            # previously preempted on this lane: its parked KV migrates too
            st.migrated = True
            spilled[rid] = st
            nbytes += st.nbytes
        self._spilled = {}
        reqs = sorted(self.waiting, key=lambda r: r.seq)
        self.waiting = []
        return reqs, spilled, nbytes

    def _advance_prefill(self, job: _PrefillJob):
        """Stream one prompt chunk through end -> link -> cloud, booking the
        same ``StageTimeline`` resources as decode (prefill is pipeline
        occupancy, not a stall)."""
        req, slot = job.req, job.slot
        S = len(req.prompt)
        C = self.prefill_chunk
        p0 = job.pos
        v = min(C, S - p0)
        self.end_pool.map_range(slot, p0, p0 + v)
        self.cloud_pool.map_range(self._cslot(slot), p0, p0 + v)
        chunk = np.zeros((C,), np.int32)
        chunk[:v] = req.prompt[p0 : p0 + v]
        tokens = jnp.asarray(chunk)[None]
        start = jnp.asarray([p0], jnp.int32)
        valid = jnp.asarray([v], jnp.int32)

        eargs = (
            (self._emask_dev, self._eres()) if self._expert_pooled else ()
        )
        t0 = time.perf_counter()
        z, self._end_pages = self._end_prefill_chunk(
            self.end_params, tokens, self._end_pages,
            self.end_pool.device_rows([slot]), start, valid, *eargs,
        )
        with span("sync"):
            payload_block_until_ready(z)
        te = self._stage_seconds("end", v)
        if te is None:
            te = time.perf_counter() - t0

        # meter only the valid rows: padding never crosses the wire.  A
        # quantized boundary is a (codes, scale) tuple — both cross the wire
        nbytes = sum(
            int(l.dtype.itemsize * int(np.prod(l.shape[2:]))) * v
            for l in (z if isinstance(z, tuple) else (z,))
        )
        t_comm = self._link_transfer(nbytes)

        t1 = time.perf_counter()
        ids, self._cloud_pages = self._cloud_prefill_chunk(
            self.cloud_params, z, self._cloud_pages,
            self.cloud_pool.device_rows([self._cslot(slot)]), start, valid,
        )
        with span("sync"):
            ids.block_until_ready()
        tc = self._stage_seconds("cloud", v)
        if tc is None:
            tc = time.perf_counter() - t1

        done_e = self.timeline.occupy(self._res_end, job.ready_s, te)
        done_l = self.timeline.occupy(self._res_link, done_e, t_comm)
        done_c = self.timeline.occupy(self._res_cloud, done_l, tc)
        job.ready_s = done_c
        self._prefill_busy["end"] += te
        self._prefill_busy["link"] += t_comm
        self._prefill_busy["cloud"] += tc
        self.n_prefill_chunks += 1

        job.pos += v
        if job.pos >= S:
            if req.prefill_done_time is None:
                # the last chunk's cloud call has returned; on the modeled
                # axis the prompt is done when that chunk drains the cloud
                req.prefill_done_time = (
                    done_c if self._virtual_time else self.clock()
                )
            # stash the DEVICE scalar; the tick's single batched
            # device->host transfer resolves it (_resolve_prefill_tokens)
            job.first_tok_dev = ids[0]
            # first token id back to the end tier
            self.link.record_down(element_bytes(jnp.int32))

    def _resolve_prefill_tokens(self):
        """Resolve every finished prefill job's first-token device scalar
        in ONE batched device->host transfer — per-job ``int(...)`` pulls
        were a per-request host sync on the prefill critical path."""
        pend = [
            (slot, job)
            for slot, job in sorted(self._jobs.items())
            if job.first_tok_dev is not None
        ]
        if not pend:
            return
        with span("sync"):
            host = jax.device_get([job.first_tok_dev for _, job in pend])
        self.n_host_syncs += 1
        for (_slot, job), tok in zip(pend, host):
            job.first_tok = int(tok)
            job.first_tok_dev = None

    def _activate_ready_jobs(self):
        """Finished prefill jobs claim their slot at the group's next
        drained tick (never while the group's boundary is in flight: the
        pending cloud-step must see the pre-activation batch state)."""
        for slot in sorted(self._jobs):
            job = self._jobs[slot]
            if job.first_tok is None or self._phase[job.group] != "ready":
                continue
            req, tok = job.req, job.first_tok
            req.generated.append(tok)
            if self._virtual_time:
                # stamp on the modeled axis: the first token exists when
                # the last prefill chunk drains the cloud stage
                self.clock.now = job.ready_s
            if req.first_token_time is None:
                req.first_token_time = self.clock()
            del self._jobs[slot]
            if tok == req.eos_id or len(req.generated) >= req.max_new_tokens:
                req.finish_time = self.clock()
                self.finished.append(req)
                self._release_slot(slot)
                continue
            self._slot_len[slot] = len(req.prompt)
            self.slots[slot] = req
            self._next_token[slot, 0] = tok
            self._active[slot] = True
            if self._spec_plan_k > 1:
                self._install_draft(slot)
            if self._virtual_time:
                # the group's next decode step cannot start before this
                # request's prefill finished feeding it
                self._group_ready_s[job.group] = max(
                    self._group_ready_s[job.group], job.ready_s
                )

    def _release_slot(self, slot: int):
        self.end_pool.free(slot)
        self.cloud_pool.free(self._cslot(slot))
        self._slot_len[slot] = 0
        self._draft_ready[slot] = False

    def busy(self) -> bool:
        return super().busy() or bool(self._jobs)

    def _progress_sig(self) -> tuple:
        # pipeline stages, prefill chunks, spill/restore churn and retries
        # all count as forward progress — only a tick that moves *none* of
        # these is a livelock candidate
        return super()._progress_sig() + (
            self.n_stage_steps,
            self.n_prefill_chunks,
            self.n_preemptions,
            self.n_preempt_restores,
            self.n_migration_restores,
            self.transfer_retries,
            self.n_expert_prefetches if self._expert_pooled else 0,
            self._spec_state.rounds if self._spec_state else 0,
            self._spec_state.rollbacks if self._spec_state else 0,
        )

    def stall_diagnostic(self) -> str:
        return (
            super().stall_diagnostic()
            + f" jobs={sorted(self._jobs)} spilled={len(self._spilled)}"
            + f" phases={list(self._phase)}"
            + f" pages_end={self.end_pool.pages_available}"
            + f" pages_cloud={self.cloud_pool.pages_available}"
            + f" link_degraded={self.link_degraded}"
        )

    # -- pipelined stepping ---------------------------------------------------

    def _group_active(self, g: int) -> bool:
        gs, ge = self._group_slices[g]
        return bool(self._active[gs:ge].any())

    def _stage_seconds(self, stage: str, batch: int) -> Optional[float]:
        """Modeled per-step service time for ``timing="modeled"`` (None in
        measured mode): batch tokens through this tier's block range at the
        device's capability rate.  The cloud rate is un-share-scaled back to
        one server — contention across fleet lanes is the timeline's job
        (multi-server queue), not the service time's."""
        if self.timing != "modeled":
            return None
        lg = self.tiers.layer_gflops
        s = self.split
        if stage == "end":
            gflops = batch * sum(lg[:s])
            rate = self.tiers.end_cap.gflop_budget * 1e3
        else:
            gflops = batch * sum(lg[s:])
            rate = (
                self.tiers.cloud_cap.gflop_budget
                / max(self._cloud_share, 1e-12)
                * 1e3
            )
        return gflops / max(rate, 1e-9)

    def _link_transfer(self, nbytes: int) -> float:
        """Meter one boundary upload, retrying injected transfer failures
        under the health monitor's bounded exponential backoff.  Every
        resend crosses the wire again, so the failed attempts' bytes are
        metered honestly rather than vanishing from the traffic report.
        Raises after ``max_transfer_attempts`` — a link that eats every
        retry is a blackout, and wedging silently here is exactly the
        failure mode the stall guard exists to catch."""
        # the per-transfer round trip (propagation + handshake) rides on
        # every attempt — it is precisely what speculative decode amortizes
        # over k tokens in the link-bound regime
        total = self.link_rtt_s + self.link.record_up(nbytes, self.bw.gbps)
        attempt = 0
        while self._transfer_faults > 0:
            self._transfer_faults -= 1
            if attempt + 1 >= self.health.max_transfer_attempts:
                raise RuntimeError(
                    f"boundary transfer failed {attempt + 1} times "
                    f"(max_transfer_attempts="
                    f"{self.health.max_transfer_attempts}); link presumed dead"
                )
            total += self.health.backoff_s(attempt)
            total += self.link_rtt_s + self.link.record_up(nbytes, self.bw.gbps)
            self.transfer_retries += 1
            attempt += 1
        return total

    def inject_transfer_faults(self, count: int):
        """Arm ``count`` boundary-transfer failures: each upcoming upload
        consumes pending faults one per attempt, retrying with backoff."""
        if count < 1:
            raise ValueError(f"count must be >= 1, got {count}")
        self._transfer_faults += count

    def _run_end_stage(self, g: int):
        k = self._spec_round_k(g)
        if k > 1:
            self._run_end_stage_spec(g, k)
            return
        gs, ge = self._group_slices[g]
        for slot in range(gs, ge):
            if self._active[slot]:
                self.end_pool.append(slot, int(self._slot_len[slot]))
                self.cloud_pool.append(self._cslot(slot), int(self._slot_len[slot]))
        tokens = jnp.asarray(self._next_token[gs:ge])
        table = self.end_pool.device_rows(
            range(gs, ge), active=self._active[gs:ge]
        )
        lengths = jnp.asarray(self._slot_len[gs:ge], jnp.int32)
        t0 = time.perf_counter()
        if self._expert_pooled:
            z, self._end_pages, stats = self._end_step(
                self.end_params, tokens, self._end_pages, table, lengths,
                self._emask_dev, self._eres(),
            )
        elif self._route_stats_enabled:
            z, self._end_pages, stats = self._end_step(
                self.end_params, tokens, self._end_pages, table, lengths
            )
        else:
            z, self._end_pages = self._end_step(
                self.end_params, tokens, self._end_pages, table, lengths
            )
            stats = None
        with span("sync"):
            payload_block_until_ready(z)
        te = self._stage_seconds("end", ge - gs)
        if te is None:
            te = time.perf_counter() - t0
        if stats is not None:
            self._observe_route_stats(stats)

        # meter only active slots' boundary rows: inactive and padding
        # slots' activations never cross the wire (matches the prefill
        # valid-rows metering and the active-only token downlink)
        per_row = sum(
            int(l.size // l.shape[0] * l.dtype.itemsize)
            for l in (z if isinstance(z, tuple) else (z,))
        )
        n_active = int(self._active[gs:ge].sum())
        nbytes = per_row * n_active
        t_comm = self._link_transfer(nbytes)
        if self._expert_pooled:
            # per-lane routed-token weight for the fleet's expert_hit_rate
            # (tokens that actually exercised the pooled end tier)
            self.expert_routed_tokens += n_active

        done_e = self.timeline.occupy(self._res_end, self._group_ready_s[g], te)
        done_l = self.timeline.occupy(self._res_link, done_e, t_comm)
        m_e = self._metric_clock.occupy("end", self._m_group_ready[g], te)
        self._m_boundary_ready[g] = self._metric_clock.occupy("link", m_e, t_comm)
        self._stage_busy["end"] += te
        self._stage_busy["link"] += t_comm
        self.n_stage_steps += 1

        self._boundary[g] = z
        self._boundary_ready_s[g] = done_l
        self._phase[g] = "boundary"

    def _drain_cloud_stage(self, g: int) -> Dict:
        """Run the cloud half of an in-flight boundary and return a drain
        record.  The token ids stay ON DEVICE — ``_harvest_drained``
        resolves every group's ids in one batched transfer per tick, so a
        lane with four groups pays one host sync where it paid four."""
        if self._spec_pending[g] is not None:
            return self._drain_cloud_stage_spec(g)
        gs, ge = self._group_slices[g]
        z = self._boundary[g]
        table = self.cloud_pool.device_rows(
            [self._cslot(s) for s in range(gs, ge)],
            active=self._active[gs:ge],
        )
        lengths = jnp.asarray(self._slot_len[gs:ge], jnp.int32)
        t0 = time.perf_counter()
        ids_dev, self._cloud_pages = self._cloud_step(
            self.cloud_params, z, self._cloud_pages, table, lengths
        )
        with span("sync"):
            ids_dev.block_until_ready()
        tc = self._stage_seconds("cloud", ge - gs)
        if tc is None:
            tc = time.perf_counter() - t0

        done_c = self.timeline.occupy(self._res_cloud, self._boundary_ready_s[g], tc)
        self._m_group_ready[g] = self._metric_clock.occupy(
            "cloud", self._m_boundary_ready[g], tc
        )
        self._stage_busy["cloud"] += tc
        self._group_ready_s[g] = done_c
        n_active = int(self._active[gs:ge].sum())
        # token ids back to the end tier — only slots that actually decoded
        # (inactive slots send nothing; metering them overcharged the link)
        self.link.record_down(n_active * element_bytes(jnp.int32))

        self._boundary[g] = None
        self._phase[g] = "ready"

        active_idx = np.nonzero(self._active[gs:ge])[0] + gs
        self._slot_len[active_idx] += 1
        return {"g": g, "kind": "plain", "done_c": done_c, "dev": (ids_dev,)}

    def _harvest_drained(self, records: List[Dict]) -> int:
        """Host side of the tick's drained boundaries: ONE batched
        device->host transfer for every group's token ids (and, for
        speculative rounds, the draft tokens), then per-group commit in
        drain order — plain groups harvest directly, speculative groups go
        through accept/rollback (:meth:`_spec_commit`)."""
        with span("sync"):
            host = jax.device_get([rec["dev"] for rec in records])
        self.n_host_syncs += 1
        emitted = 0
        for rec, dev in zip(records, host):
            if self._virtual_time:
                # finish stamps for this group land at its cloud completion
                self.clock.now = rec["done_c"]
            if rec["kind"] == "plain":
                gs, ge = self._group_slices[rec["g"]]
                ids = np.zeros((self.max_batch,), np.int64)
                ids[gs:ge] = np.asarray(dev[0])
                emitted += self._harvest(ids, slot_range=range(gs, ge))
            else:
                drafts, verify = (np.asarray(a) for a in dev)
                emitted += self._spec_commit(rec, drafts, verify)
        return emitted

    def _run_cloud_stage(self, g: int) -> int:
        """Drain one group's boundary and harvest immediately — the
        single-group form (tests and targeted drains); ``step`` batches
        all drained groups through one ``_harvest_drained`` call."""
        return self._harvest_drained([self._drain_cloud_stage(g)])

    def step(self) -> int:
        """One engine tick: drain in-flight boundaries on the cloud tier,
        apply a pending replan at the safe point, admit (page-aware), stream
        one prefill chunk per in-flight job, activate finished jobs, then
        refill the end tier — so group A's cloud-step overlaps group B's
        end-step and a long prompt's prefill never stalls other groups'
        decode."""
        emitted = 0
        with span("step"):
            if self.link_degraded:
                self.degraded_ticks += 1
            drained = []
            for g in range(self.n_groups):
                if self._phase[g] == "boundary":
                    with span("drain", group=g):
                        drained.append(self._drain_cloud_stage(g))
            if drained:
                with span("harvest"):
                    emitted += self._harvest_drained(drained)
            with span("prefetch"):
                self._advance_expert_prefetch()
            with span("replan"):
                self._apply_pending_replan()
            with span("admit"):
                self._admit()
            for slot in sorted(self._jobs):
                job = self._jobs[slot]
                if job.first_tok is None and job.first_tok_dev is None:
                    with span("prefill", req=job.req.request_id, slot=slot):
                        self._advance_prefill(job)
            with span("resolve"):
                self._resolve_prefill_tokens()
            if self._spec_plan_k > 1:
                with span("draft"):
                    self._spec_refresh_drafts()
            with span("activate"):
                self._activate_ready_jobs()
            for g in range(self.n_groups):
                if self._phase[g] == "ready" and self._group_active(g):
                    with span("end_stage", group=g):
                        self._run_end_stage(g)
        return emitted

    # -- dynamic replanning ---------------------------------------------------

    def observe_bandwidth(self, gbps: float, *, hard: bool = False):
        """Feed a link measurement (e.g. from a probe or the paper's TC
        setup); triggers a replan check against measured conditions.
        ``hard=True`` bypasses the EWMA — a *declared* link event (chaos
        injection, a blackout beginning or ending) is a fact, not a noisy
        sample, and must take effect at the next safe point rather than
        after the estimator converges."""
        if hard:
            self.bw.set_rate(gbps)
            # the blackout ladder keys on DECLARED rates only: a soft EWMA
            # observation — however low — is a measurement the ordinary
            # replanner answers (e.g. by moving to a compressed interior
            # split; see benchmarks.fleet_throughput phase 2), not a
            # declared wire-down event
            self._update_link_health()
        else:
            self.bw.observe_rate(gbps)
        if not self.link_degraded:
            self._check_replan()
        # the draft-length plan tracks the same measured link conditions:
        # a fattening link turns speculation off (compute-bound), a
        # thinning one turns it on or lengthens the draft
        self._recompute_spec_plan()

    def _update_link_health(self):
        """Degradation ladder, bottom rung: when the estimated link rate
        falls below ``blackout_gbps``, pin the plan to split 0 (cloud-only;
        the boundary payload collapses to token ids) instead of letting the
        planner keep an interior split that would wedge every boundary
        behind a dead wire.  The planner itself would not choose this —
        boundary bytes are split-independent, so it sees no gain — which is
        why the rung is explicit policy, not planning.  On recovery the
        normal replan path resumes and unwinds the pin at the next safe
        point."""
        blacked = self.bw.gbps < self.blackout_gbps
        if blacked and not self.link_degraded:
            self.link_degraded = True
            self._blackout_since = self.clock()
            plan = plan_pipeline_split(
                self.tiers.layer_gflops,
                self.tiers.boundary_bytes,
                dataclasses.replace(self.tiers.end_cap, net_gbps=self.bw.gbps),
                self.tiers.cloud_cap,
                compression_ratio=self.tiers.compression_ratio,
                alpha=self.tiers.alpha,
                edge_boundary=True,
                pin_split=0,
            )
            self._pending_plan = plan
        elif not blacked and self.link_degraded:
            self.link_degraded = False
            self.link_blackout_s += max(0.0, self.clock() - self._blackout_since)
            self._check_replan(force=True)

    def blackout_seconds(self) -> float:
        """Total wall-clock spent under a blacked-out link, including a
        still-open window."""
        open_s = (
            max(0.0, self.clock() - self._blackout_since)
            if self.link_degraded
            else 0.0
        )
        return self.link_blackout_s + open_s

    def set_cloud_share(self, share: float):
        """Re-scale this lane's slice of the total cloud budget (a cloud
        server died or rejoined).  Per-server service time in
        ``_stage_seconds`` is unchanged — budget and share scale together —
        but the planner's view of aggregate cloud capacity shrinks, so the
        split may move at the next safe point."""
        old = max(self._cloud_share, 1e-12)
        self.tiers = dataclasses.replace(
            self.tiers,
            cloud_cap=dataclasses.replace(
                self.tiers.cloud_cap,
                gflop_budget=self.tiers.cloud_cap.gflop_budget * share / old,
            ),
        )
        self._cloud_share = share
        if not self.link_degraded:
            self._check_replan()

    def update_device_state(self, end_state: DeviceState):
        """Feed a new end-device state vector (eq. 2): re-derive the end
        capability AND the hardware-aware expert mask (eq. 2-4), then
        re-check the plan.  Mask changes are applied at the same safe point
        as split changes."""
        new_mask = self._derive_end_mask(end_state)
        # same loud rejection as the construction-time boundary: a state so
        # degraded that eq. 4 admits nothing must not silently become a
        # uniform-renormalized gate (dense) or all-garbage routing (pooled).
        # Validated before any engine state moves, so a rejected update
        # leaves the running plan untouched.
        validate_expert_mask(
            new_mask,
            self.cfg.moe.num_experts if self.cfg.moe is not None else None,
            where="update_device_state(end_mask)",
        )
        self.end_state = end_state
        self.tiers = dataclasses.replace(
            self.tiers, end_cap=capability(self.end_profile, end_state)
        )
        mask_changed = not _masks_equal(new_mask, self.tiers.end_mask)
        if mask_changed:
            self._pending_mask = new_mask
        else:
            # latest state agrees with the applied mask: cancel any pending
            # change from an earlier (now recovered-from) observation
            self._pending_mask = _KEEP
        if self._expert_pooled:
            # the memory budget (slab capacity) may have moved even when the
            # eq. 4 mask did not: reconcile at the next safe point, and start
            # any newly-needed slab transfers NOW so they overlap decode.
            # The capacity is adopted immediately — set_capacity never
            # evicts, so raising it unblocks transfers during the window
            # before the safe point, and lowering it only pauses allocs
            # until the safe-point evictions land
            self._expert_dirty = True
            self.expert_pool.set_capacity(self._expert_capacity())
            target = np.asarray(
                new_mask if mask_changed else self.tiers.end_mask, bool
            )
            wanted, _ev = self._plan_residency(self._active_lids(), target)
            self._prefetch_queue = list(wanted)
        # The state vector's B_bw component is a link observation only when
        # it reports a non-default value; a default-constructed 1.0 means
        # "not measured" and must not overwrite probe readings fed through
        # observe_bandwidth (report recovery explicitly via either channel).
        if end_state.bandwidth_free != 1.0:
            self.bw.observe_rate(self.tiers.end_cap.net_gbps)
        self._check_replan(force=mask_changed)

    def _check_replan(self, force: bool = False):
        if self.link_degraded:
            # the degradation ladder owns the plan while the link is dark:
            # the pinned split-0 plan must not be displaced by a replan
            # computed from a near-zero rate (mask changes still flow
            # through _pending_mask and the safe point as usual)
            return
        # planning inputs come from TierPlan so replanning uses exactly the
        # cost model the initial plan was computed with
        plan, changed = replan_pipeline(
            self.plan,
            self.tiers.layer_gflops,
            self.tiers.boundary_bytes,
            self.tiers.end_cap,
            self.tiers.cloud_cap,
            measured_gbps=self.bw.gbps,
            compression_ratio=self.tiers.compression_ratio,
            alpha=self.tiers.alpha,
            rel_threshold=self.replan_threshold,
            edge_boundary=True,
        )
        trace_changed = (
            plan.split_layer != self.plan.split_layer
            or plan.compress_boundary != self.plan.compress_boundary
        )
        if changed or trace_changed or force:
            # needs the drained safe point (and possibly a re-split/rebuild)
            self._pending_plan = plan
        else:
            # current split/codec stand: drop any stale pending change and
            # adopt the refreshed estimates in place (nothing a trace
            # captures differs, so no rebuild is needed)
            self._pending_plan = None
            self.tiers = dataclasses.replace(self.tiers, plan=plan)

    def _defrag_private_pools(self):
        """Compact the engine-private pools and permute their storage rows
        to match.  A fleet-shared cloud pool is never defragged here — its
        permutation would have to be applied to every lane's storage (see
        ``FleetServingEngine.defrag_kv``)."""
        perm = self.end_pool.defrag()
        self._end_pages = jax.tree.map(
            lambda l: l[:, jnp.asarray(perm)], self._end_pages
        )
        if not self._cloud_shared:
            perm = self.cloud_pool.defrag()
            self._cloud_pages = jax.tree.map(
                lambda l: l[:, jnp.asarray(perm)], self._cloud_pages
            )

    def _apply_pending_replan(self):
        """Adopt a pending plan/mask once no boundary is in flight (both
        tiers at equal ``lengths``): re-split params at the new block
        boundary, move the affected blocks' pages between the tier pools
        (table-aware row permutation), defrag the private pools, and rebuild
        the stage functions — but only when something a trace captures
        (split, codec flag, expert mask) actually changed."""
        if (
            self._pending_plan is None
            and self._pending_mask is _KEEP
            and not (self._expert_pooled and self._expert_dirty)
        ):
            return
        if any(p == "boundary" for p in self._phase):
            return
        had_pending = (
            self._pending_plan is not None or self._pending_mask is not _KEEP
        )
        plan = self._pending_plan or self.plan
        self._pending_plan = None
        old_split = self.split
        old_compress = self.tiers.compress
        mask_changed = self._pending_mask is not _KEEP
        updates: Dict = {"plan": plan}
        if mask_changed:
            updates["end_mask"] = self._pending_mask
            self._pending_mask = _KEEP
        self.tiers = dataclasses.replace(self.tiers, **updates)
        if mask_changed:
            # the draft model speculates under the end mask: a new mask
            # invalidates every draft cache (they hold old-mask KV)
            self._draft_ready[:] = False
        if self.split != old_split:
            self.end_params, self.cloud_params = split_block_params(
                self.params, self.split
            )
            if self._expert_pooled:
                self.end_params = strip_expert_weights(self.end_params, self.cfg)
            cloud_rows = self.cloud_pool.table[
                self._cloud_base : self._cloud_base + self.max_batch
            ]
            e2c = kvcache.page_perm(
                self.end_pool.table, cloud_rows,
                self.end_pool.num_pages, self.cloud_pool.num_pages,
            )
            c2e = kvcache.page_perm(
                cloud_rows, self.end_pool.table,
                self.cloud_pool.num_pages, self.end_pool.num_pages,
            )
            self._end_pages, self._cloud_pages = kvcache.resplit_paged_blocks(
                self._end_pages, self._cloud_pages, old_split, self.split,
                e2c, c2e,
            )
            self._defrag_private_pools()
        if self._expert_pooled:
            # blocks entering the end tier materialize their target
            # residents with the (unmetered) block re-split; every other
            # residency change rides the prefetch queue / eviction plan
            instant = set()
            if self.split > old_split:
                R = self.cfg.block_repeat
                instant = {
                    pi * R + b
                    for pi in range(len(self._moe_pos))
                    for b in range(old_split, self.split)
                }
            self._expert_sync(instant_lids=instant)
        if (
            self.split != old_split
            or self.tiers.compress != old_compress
            or (mask_changed and not self._expert_pooled)
        ):
            # pooled engines take the mask/tables as runtime operands, so a
            # mask-only change needs no rebuild (and no retrace)
            self._build_stage_fns()
        else:
            self._recompute_spec_plan()
        if had_pending:
            self.replan_events.append(
                {
                    "old_split": old_split,
                    "new_split": self.split,
                    "measured_gbps": self.bw.gbps,
                    "compress": self.tiers.compress,
                    "mask_changed": mask_changed,
                }
            )

    # -- metrics --------------------------------------------------------------

    def stage_trace_counts(self) -> Dict[str, int]:
        """Distinct compiled-trace signatures per stage function, summed
        across stage-function rebuilds.  Bounded by chunk/group shapes —
        independent of how many distinct prompt lengths were served."""
        return {k: len(v) for k, v in self._traces.items()}

    def attn_bytes_step(self) -> Dict[str, int]:
        """KV bytes the attention sweep moves from HBM per decode step
        (both tiers, all layers) at the current occupancy.  The fused paged
        path reads only this engine's *mapped* pages; the dense-gather path
        it replaced materialized and swept the full ``slots x ring`` view
        every step (counted as one sweep read — the gather's extra HBM
        write of the same bytes is not charged, so the comparison is
        conservative; the dense baseline uses the user-visible slot count,
        matching ``kv_bytes_dense_equiv``).  The dense baseline is priced at
        the dense page size (``kvcache.dense_page_bytes``) regardless of the
        stored pool's dtype — quantizing the pool must shrink the numerator,
        never the denominator."""
        own_cloud = range(self._cloud_base, self._cloud_base + self.max_batch)
        end_pb = kvcache.paged_block_bytes(self._end_pages)
        cloud_pb = kvcache.paged_block_bytes(self._cloud_pages)
        dense_pb = self._dense_page_bytes()
        return {
            "attn_bytes_paged_step": (
                self.end_pool.pages_in_use * end_pb
                + self.cloud_pool.mapped_for(own_cloud) * cloud_pb
            ),
            "attn_bytes_dense_step": (
                self.request_capacity * self.pages_per_slot * dense_pb
            ),
        }

    def _dense_page_bytes(self) -> int:
        """Per-page bytes across both tiers at the dense KV dtype (the
        stable denominator for the quantized pools' capacity ratio)."""
        R = self.cfg.block_repeat
        return kvcache.dense_page_bytes(
            self.cfg, self.split, self.page_size
        ) + kvcache.dense_page_bytes(
            self.cfg, R - self.split, self.page_size
        )

    def _expert_hit_rate(self) -> float:
        """Route-frequency-weighted residency coverage of the current
        target set: 1.0 once every target expert of every active end layer
        is resident.  Frequencies are the measured EMA plus a uniform
        ``1/E`` prior, so experts the target just admitted (no traffic
        measured yet — they could not be routed to) still register as
        misses until their slab lands."""
        if not self._expert_pooled:
            return 1.0
        E = self.cfg.moe.num_experts
        f = (
            self._route_freq if self._route_freq is not None
            else np.zeros((E,))
        ) + 1.0 / E
        t = self._target_mask_np()
        num = den = 0.0
        for lid in self._active_lids():
            r = self.expert_pool.resident_mask(lid)
            num += float(f[t & r].sum())
            den += float(f[t].sum())
        return 1.0 if den == 0.0 else num / den

    def expert_metrics(self) -> Dict[str, float]:
        """Paged expert-weight accounting: residency, hit rate, transfer
        traffic, and the per-decode-step expert HBM bytes the resident
        gather moves vs the dense ``[E, d, f]`` sweep it replaced (the
        garbage slab — one shared zeros row — is not charged)."""
        if not self._expert_pooled:
            return {}
        pool = self.expert_pool
        active = self._active_lids()
        sb = self._slab_bytes
        sbd = self._slab_bytes_dense
        E = self.cfg.moe.num_experts
        n_res_active = sum(pool.resident_count(lid) for lid in active)
        return {
            "expert_resident_slabs": pool.slabs_in_use,
            "expert_slab_capacity": pool.capacity,
            "expert_hit_rate": self._expert_hit_rate(),
            "expert_bytes_down": self.expert_bytes_down,
            "expert_bytes_peer": self.expert_bytes_peer,
            "expert_bytes_up": self.expert_bytes_up,
            "expert_bytes_resident": pool.slabs_in_use * sb,
            "expert_bytes_step_resident": n_res_active * sb,
            # the dense sweep baseline holds full-precision weights — it
            # must not shrink when the slab store is quantized
            "expert_bytes_step_dense": len(active) * E * sbd,
            "expert_slab_bytes": sb,
            "expert_slab_bytes_dense": sbd,
            # effective capacity: how many stored slabs fit per dense slab
            "expert_capacity_ratio": sbd / sb,
            "expert_quantized": float(self.quantize_experts),
            "expert_prefetches": self.n_expert_prefetches,
            "expert_peer_fetches": self.n_expert_peer_fetches,
            "expert_evictions": self.n_expert_evictions,
            "expert_routed_tokens": self.expert_routed_tokens,
        }

    def kv_metrics(self) -> Dict[str, float]:
        """Paged-KV memory accounting.  With a fleet-shared cloud pool the
        in-use/capacity figures for the cloud tier count only this lane's
        rows; ``kv_bytes_peak`` uses the pools' global peaks (the shared
        pool peaks fleet-wide — that is the number admission gates on)."""
        own_cloud = range(self._cloud_base, self._cloud_base + self.max_batch)
        end_pb = kvcache.paged_block_bytes(self._end_pages)
        cloud_pb = kvcache.paged_block_bytes(self._cloud_pages)
        dense_pb = self._dense_page_bytes()
        in_use = self.end_pool.pages_in_use + self.cloud_pool.mapped_for(own_cloud)
        cap = self.end_pool.num_pages + self.cloud_pool.num_pages
        return {
            **self.attn_bytes_step(),
            "kv_pages_in_use": in_use,
            "kv_pages_capacity": cap,
            "kv_utilization": in_use / cap,
            "kv_bytes_peak": (
                self.end_pool.peak_in_use * end_pb
                + self.cloud_pool.peak_in_use * cloud_pb
            ),
            # the honest pre-refactor baseline: dense rings at the dense
            # dtype for the user-visible slot count (padding slots and the
            # quantized pool layout are this repo's artifacts)
            "kv_bytes_dense_equiv": (
                self.request_capacity * self.pages_per_slot * dense_pb
            ),
            "kv_page_bytes": end_pb + cloud_pb,
            "kv_page_bytes_dense": dense_pb,
            # effective capacity: how many stored pages fit per dense page
            "kv_capacity_ratio": dense_pb / (end_pb + cloud_pb),
            "kv_quantized": float(self.quantize_kv),
        }

    def metrics(self) -> Dict[str, float]:
        n = max(self.n_stage_steps, 1)
        mean = {r: t / n for r, t in self._stage_busy.items()}
        # This engine's own pipelined DECODE span, from the decode-only
        # metric clock: free of other lanes' time when the timeline is
        # fleet-shared, and free of interleaved prefill-chunk occupancy.
        # serial likewise sums only this engine's decode stages.
        pipelined_total = max(self._m_group_ready)
        serial_total = sum(self._stage_busy.values())
        return {
            "split": self.split,
            "compressed": self.tiers.compress,
            "boundary_quantized": float(self.quantize_boundary),
            "n_groups": self.n_groups,
            "bytes_up": self.link.bytes_up,
            "transfers": self.link.transfers,
            "n_stage_steps": self.n_stage_steps,
            "mean_t_end_s": mean["end"],
            "mean_t_comm_s": mean["link"],
            "mean_t_cloud_s": mean["cloud"],
            # serial layout vs the pipelined resource-occupancy schedule
            "serial_step_s": mean["end"] + mean["link"] + mean["cloud"],
            "pipelined_step_s": pipelined_total / n,
            "plan_est_step_s": self.plan.est_step_time_s,
            "pipelined_total_s": pipelined_total,
            "serial_total_s": serial_total,
            "prefill_s": sum(self._prefill_busy.values()),
            "prefill_chunks": self.n_prefill_chunks,
            "preemptions": self.n_preemptions,
            "preempt_restores": self.n_preempt_restores,
            "preempt_spill_bytes": self.preempt_spill_bytes,
            "migration_restores": self.n_migration_restores,
            "transfer_retries": self.transfer_retries,
            "degraded_ticks": self.degraded_ticks,
            "link_blackout_s": self.blackout_seconds(),
            "replan_events": len(self.replan_events),
            "measured_gbps": self.bw.gbps,
            "n_host_syncs": self.n_host_syncs,
            "spec_plan_k": self._spec_plan_k,
            "spec_k_eff": (
                self._spec_state.k_eff if self._spec_state is not None else 1
            ),
            **(
                self._spec_state.metrics()
                if self._spec_state is not None
                else {
                    "spec_rounds": 0,
                    "spec_drafted": 0,
                    "spec_accepted": 0,
                    "spec_acceptance_rate": 0.0,
                    "spec_rollbacks": 0,
                }
            ),
            **self.kv_metrics(),
            **self.expert_metrics(),
        }
