"""Shared serving infrastructure: requests, slot bookkeeping, link metering.

Both the single-tier continuous-batching engine (``serving.engine``) and the
streaming end-cloud decode engine (``serving.stream``) are slot machines: a
fixed decode batch of ``max_batch`` slots, finished requests free their slot,
waiting requests are prefilled into free slots.  ``SlotEngineBase`` owns that
lifecycle; subclasses provide the actual prefill/decode compute.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def element_bytes(dtype) -> int:
    """Bytes per element of ``dtype`` (a jnp/np dtype, dtype class, or
    string such as ``"bfloat16"``).  The ONE place serving byte metering
    resolves element widths — no hardcoded ``* 4`` anywhere — so a stream
    carrying bf16 / int8 payloads meters half / a quarter of the f32
    bytes."""
    return jnp.dtype(dtype).itemsize


def payload_nbytes(z) -> int:
    """Total bytes of a boundary payload: a single array or a tuple of
    arrays (the quantized boundary codec ships ``(codes, scales)``)."""
    if isinstance(z, (tuple, list)):
        return sum(int(p.size) * element_bytes(p.dtype) for p in z)
    return int(z.size) * element_bytes(z.dtype)


def payload_block_until_ready(z):
    """``block_until_ready`` on a payload that may be a tuple of arrays."""
    for p in z if isinstance(z, (tuple, list)) else (z,):
        p.block_until_ready()


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int = 16
    eos_id: int = -1  # -1 = never
    # SLO class: lower ``priority`` admits first (0 = interactive).  The
    # per-request latency targets are carried for reporting/accounting —
    # the engine schedules by class, the load harness scores the targets.
    priority: int = 1
    ttft_slo_s: Optional[float] = None
    tpot_slo_s: Optional[float] = None
    # filled by the engine
    generated: List[int] = field(default_factory=list)
    submit_time: float = 0.0
    # a prefill takes the request's slot, and the prompt's last chunk comes
    # back from the cloud tier: TTFT = admission wait (admit - submit) +
    # prefill (prefill_done - admit) + activation (first_token - prefill_done)
    admit_time: Optional[float] = None
    prefill_done_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    seq: int = -1  # submission order stamp (ties within a priority class)
    n_preemptions: int = 0
    n_migrations: int = 0  # lane-death migrations this request survived

    @property
    def done(self) -> bool:
        return self.finish_time is not None

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token (None until the first token lands)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token after the first (None until finished
        or for single-token generations)."""
        if self.finish_time is None or len(self.generated) < 2:
            return None
        return (self.finish_time - self.first_token_time) / (
            len(self.generated) - 1
        )


class VirtualClock:
    """Callable clock over *modeled* time.

    Engines stamp request lifecycle times (submit / first token / finish)
    with ``self.clock()``; by default that is host wall time.  Handing an
    engine a ``VirtualClock`` switches those stamps onto the engine's
    ``StageTimeline`` axis: the engine detects it and sets ``now`` to the
    modeled completion time of the stage that produced each event, so
    TTFT/TPOT are measured on the same deterministic clock the schedule is
    computed on.  The load harness (``serving.loadgen.drive``) owns the
    submission side: it releases arrivals when ``now`` passes their arrival
    time and advances ``now`` to the timeline makespan after each tick.
    """

    def __init__(self, now: float = 0.0):
        self.now = now

    def __call__(self) -> float:
        return self.now

    def advance_to(self, t: float) -> float:
        """Monotone advance (jumping backwards is reserved for engines
        stamping a specific stage completion)."""
        self.now = max(self.now, t)
        return self.now


@dataclass
class LinkStats:
    """Meter for the end<->cloud link: bytes on the wire in each direction
    plus modeled wire seconds.  In a real two-host deployment the measured
    (bytes, seconds) pairs are what you feed to
    ``core.pipeline.BandwidthEstimator.observe`` for replanning."""

    bytes_up: int = 0
    bytes_down: int = 0
    bytes_peer: int = 0
    transfers: int = 0
    seconds_up: float = 0.0
    seconds_peer: float = 0.0

    def transfer_time(self, nbytes: int, gbps: float) -> float:
        return nbytes * 8.0 / max(gbps * 1e9, 1e-9)

    def record_peer(self, nbytes: int, seconds: float) -> None:
        """Meter an end<->end transfer (peer expert-slab fetch — the wire
        time is modeled by the fleet registry's peer-link cost, so it is
        recorded rather than derived from the cloud uplink rate)."""
        self.bytes_peer += nbytes
        self.seconds_peer += seconds

    def record_up(self, nbytes: int, gbps: float) -> float:
        """Meter an end->cloud transfer; returns its modeled wire time."""
        t = self.transfer_time(nbytes, gbps)
        self.bytes_up += nbytes
        self.transfers += 1
        self.seconds_up += t
        return t

    def record_down(self, nbytes: int) -> None:
        """Meter a cloud->end transfer (token-id feedback — bytes only; at
        ~4 bytes/token its wire time is noise next to the boundary uplink)."""
        self.bytes_down += nbytes

    @property
    def measured_gbps(self) -> float:
        """Average realized uplink rate over everything metered so far."""
        return self.bytes_up * 8.0 / max(self.seconds_up * 1e9, 1e-12)


class StageTimeline:
    """Resource-occupancy clock for the decode pipeline (same queueing model
    as ``sim.simulator``: a stage starts at max(input-ready, resource-free)).

    The streaming engine feeds it measured compute times and modeled link
    times; the resulting makespan is the *pipelined* schedule, while
    ``serial_s`` accumulates the same stages laid end to end — the spread
    between the two is exactly the overlap the double buffer buys.

    A resource may have multiple servers (``capacity``), and each server
    books jobs into *busy intervals*: a job starts in the earliest gap at
    or after its ready time (backfill).  Interval booking — rather than a
    single ratcheting free-time per server — matters because callers may
    arrive out of virtual-time order: fleet lanes advance their own clocks
    at different rates, so a slow lane can book the shared cloud at t=150ms
    before a fast lane asks for t=50ms; the fast lane's job must land in
    the earlier gap, exactly as a real FCFS queue (or ``sim.simulator``'s
    event heap) would serve it.  The fleet engine uses capacity for the
    shared cloud tier (N end devices, ``cloud_servers`` cloud GPUs) and
    registers per-device end/link resources via ``add_resource``.
    """

    def __init__(
        self,
        resources: Sequence[str] = ("end", "link", "cloud"),
        capacity: Optional[Dict[str, int]] = None,
    ):
        capacity = capacity or {}
        # per resource: per server: sorted [start, end) busy intervals
        self._servers: Dict[str, List[List[Tuple[float, float]]]] = {
            r: [[] for _ in range(max(capacity.get(r, 1), 1))]
            for r in resources
        }
        self.busy_s: Dict[str, float] = {r: 0.0 for r in resources}
        self.serial_s: float = 0.0
        self._max_end = 0.0

    def add_resource(self, name: str, capacity: int = 1):
        """Register a resource if absent (idempotent; capacity of an
        existing resource is left untouched)."""
        if name not in self._servers:
            self._servers[name] = [[] for _ in range(max(capacity, 1))]
            self.busy_s[name] = 0.0

    def n_servers(self, name: str) -> int:
        return len(self._servers[name])

    def remove_server(self, name: str):
        """Drop one server from a multi-server resource (fault injection:
        a shared cloud server dies).  Work already booked on it stays in
        ``busy_s``/``makespan_s`` — it happened — but its interval list
        vanishes, so every future booking queues on the survivors.  The
        last server cannot be removed: a resource with no servers makes
        every dependent stage unserveable, which callers must handle as a
        total outage, not a capacity change."""
        servers = self._servers[name]
        if len(servers) <= 1:
            raise ValueError(
                f"resource {name!r} has a single server; removing it is a "
                "total outage, not a capacity reduction"
            )
        servers.pop()

    @staticmethod
    def _earliest_start(
        intervals: List[Tuple[float, float]], ready_s: float, service_s: float
    ) -> float:
        start = ready_s
        for s, e in intervals:
            if start + service_s <= s:
                break  # fits in the gap before this interval
            if e > start:
                start = e
        return start

    @property
    def free_at(self) -> Dict[str, float]:
        """Time each resource's earliest-draining server runs dry."""
        return {
            r: min((ivals[-1][1] if ivals else 0.0) for ivals in servers)
            for r, servers in self._servers.items()
        }

    def occupy(self, resource: str, ready_s: float, service_s: float) -> float:
        servers = self._servers[resource]
        best, best_start = 0, None
        for i, ivals in enumerate(servers):
            start = self._earliest_start(ivals, ready_s, service_s)
            if best_start is None or start < best_start:
                best, best_start = i, start
        end = best_start + service_s
        if service_s > 0:
            ivals = servers[best]
            j = bisect.bisect_left(ivals, (best_start, end))
            # coalesce with touching neighbours — the common booking is
            # contiguous at a server's tail, so lists stay short and the
            # gap scan near-O(1) instead of growing one tuple per step
            s, e = best_start, end
            if j < len(ivals) and ivals[j][0] <= e:
                e = max(e, ivals[j][1])
                del ivals[j]
            if j > 0 and ivals[j - 1][1] >= s:
                s = ivals[j - 1][0]
                e = max(e, ivals[j - 1][1])
                del ivals[j - 1]
                j -= 1
            ivals.insert(j, (s, e))
        self.busy_s[resource] += service_s
        self.serial_s += service_s
        self._max_end = max(self._max_end, end)
        return end

    @property
    def makespan_s(self) -> float:
        return self._max_end

    def summary(self) -> Dict[str, float]:
        return {
            "pipelined_s": self.makespan_s,
            "serial_s": self.serial_s,
            **{f"busy_{r}_s": t for r, t in self.busy_s.items()},
        }


class TraceCounter:
    """Counts distinct argument shape/dtype signatures seen by a jitted
    callable — each distinct signature is one compiled trace, so engines can
    assert their stage-trace count is bounded by chunk/group *shapes* rather
    than by distinct prompt lengths.  ``log`` is a caller-owned set so the
    count survives stage-function rebuilds (each rebuild passes a fresh
    ``generation`` tag: a rebuilt jit re-traces even for seen shapes).

    ``sig_from`` skips leading arguments whose shapes cannot change within
    a build — the engines pass the (large) params pytree first, and any
    params re-split comes with a rebuilt wrapper/new generation — keeping
    the per-call bookkeeping on the decode hot path to a handful of leaves.
    """

    def __init__(self, fn: Callable, log: set, generation: int = 0,
                 sig_from: int = 1):
        self._fn = fn
        self._log = log
        self._gen = generation
        self._sig_from = sig_from

    @staticmethod
    def _sig(tree) -> Tuple:
        return tuple(
            (tuple(leaf.shape), str(getattr(leaf, "dtype", type(leaf))))
            if hasattr(leaf, "shape") else (type(leaf).__name__,)
            for leaf in jax.tree.leaves(tree)
        )

    def __call__(self, *args):
        self._log.add((self._gen, self._sig(args[self._sig_from :])))
        return self._fn(*args)


SPAN_PREFIX = "engine:"
_SPAN_NAMES: Dict[str, str] = {}  # name -> "engine:" + name, built once


def span(name: str, **ids):
    """A host span ``engine:<name>`` on the profiler's clock, with ``ids``
    (a request id, a slot, a group) as its arguments.  It records only
    while a profiler trace is active, so device operations and the host
    code around them land on one clock; otherwise it records and formats
    nothing.

    The engines nest them: ``step`` holds one span per phase of the tick
    (``drain``, ``harvest``, ``prefetch``, ``replan``, ``admit``,
    ``prefill``, ``resolve``, ``draft``, ``activate``, ``end_stage``), and
    each phase holds a ``sync`` span wherever the host blocks on the
    device, so a tick's own host time is ``step`` less its ``sync``
    spans."""
    full = _SPAN_NAMES.get(name)
    if full is None:
        full = _SPAN_NAMES[name] = SPAN_PREFIX + name
    return jax.profiler.TraceAnnotation(full, **ids)


class _GcSpans:
    """A ``gc.callbacks`` hook that wraps every collection of the Python
    garbage collector in an ``engine:gc`` span with its generation, so a
    long collection inside a tick is named in a trace like any phase."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: Dict):
        if phase == "start":
            self._open = span("gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def install_gc_spans():
    """Add the ``engine:gc`` hook to ``gc.callbacks`` once per process."""
    import gc

    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())


class SlotEngineBase:
    """Slot lifecycle shared by the serving engines.

    Subclasses implement ``_prefill_into_slot(slot, req) -> (int, payload)``
    (run prefill, return the first generated token plus whatever cache state
    the slot needs) and ``_install_slot(slot, payload)`` (copy that state
    into the batch cache — called only when the request actually continues
    past prefill, so requests that finish on their first token skip the
    copy) and drive decode via ``step``; the base provides admission, token
    harvesting, and the run loop.  ``_release_slot`` is called whenever a
    request leaves its slot (finish at prefill or at decode) so paged
    engines can return the slot's KV pages to the pool.
    """

    def __init__(
        self,
        max_batch: int,
        clock: Optional[Callable[[], float]] = None,
        max_len: Optional[int] = None,
        admission: str = "priority",
    ):
        import time as _time

        if admission not in ("priority", "fifo"):
            raise ValueError(f"admission={admission!r}")
        self.max_batch = max_batch
        self.max_len = max_len
        self.clock = clock or _time.monotonic
        self.admission = admission
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        self._next_token = np.zeros((max_batch, 1), np.int32)
        self._active = np.zeros((max_batch,), bool)
        self._submit_seq = 0
        # livelock guard: busy ticks tolerated with no progress before the
        # run loop raises (see faults.StallGuard; attribute, not ctor arg,
        # so subclasses/tests tune it without threading a kwarg through)
        self.stall_limit = 256

    # -- request lifecycle ---------------------------------------------------

    def validate(self, req: Request):
        """Reject a request that cannot fit the slot's KV ring buffer: past
        ``max_len`` positions the ring wraps and silently corrupts attention,
        so over-long requests must fail loudly at submit time."""
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.request_id}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.request_id}: max_new_tokens="
                f"{req.max_new_tokens} (prefill always emits one token)"
            )
        if self.max_len is not None:
            need = len(req.prompt) + req.max_new_tokens
            if need > self.max_len:
                raise ValueError(
                    f"request {req.request_id}: prompt ({len(req.prompt)}) + "
                    f"max_new_tokens ({req.max_new_tokens}) = {need} exceeds "
                    f"max_len={self.max_len}; the KV ring buffer would wrap"
                )
        cap = self._page_capacity()
        if cap is not None:
            pages = self._pages_for(req)
            if pages > cap:
                raise ValueError(
                    f"request {req.request_id}: needs {pages} KV pages but "
                    f"the smallest page pool holds only {cap} (kv_pages too "
                    "small for prompt + max_new_tokens); it could never be "
                    "admitted and would block the FIFO queue forever"
                )

    def _page_capacity(self) -> Optional[int]:
        """Hook: total pages of the engine's most constrained pool, or None
        for dense engines.  Paired with ``_pages_for``; the base validates
        that a request's worst-case reservation can ever be satisfied."""
        return None

    def _pages_for(self, req: Request) -> int:
        raise NotImplementedError

    def submit(self, req: Request):
        self.validate(req)
        req.submit_time = self.clock()
        req.seq = self._submit_seq
        self._submit_seq += 1
        self.waiting.append(req)

    def _slot_usable(self, slot: int) -> bool:
        """Hook: is this slot index eligible to hold requests at all?
        (Engines that pad the batch for equal-sized micro-batch groups mark
        padding slots unusable; slots mid-prefill are unusable too.)"""
        return True

    def _admittable(self, slot: int, req: Request) -> bool:
        """Hook: may ``req`` be admitted into this free slot right now?
        Paged engines check KV page availability here — admission is gated
        on pages, not just on a free slot."""
        return True

    def free_slots(self) -> int:
        """Slots currently able to accept a request (excludes padding slots
        and slots held by an in-flight chunked prefill)."""
        return sum(
            1 for i, s in enumerate(self.slots)
            if s is None and self._slot_usable(i)
        )

    def busy(self) -> bool:
        """Anything left to do?  (Queued, decoding, or mid-prefill.)"""
        return bool(self.waiting) or bool(self._active.any())

    def _admission_order(self) -> List[Request]:
        """The queue view admission scans.  ``"priority"`` (default) is a
        stable sort on (priority class, submission seq): equal-priority
        requests keep FIFO order, and a page-hungry low-priority request at
        the FIFO head can no longer starve interactive traffic — higher
        classes simply sort ahead of it.  ``"fifo"`` is pure submission
        order (the pre-SLO behavior, kept as the ablation baseline).

        Either way the scan *head* blocks its whole order: admitting work
        past a page-blocked head would keep pages occupied and starve it —
        within one class, FIFO fairness is the invariant worth keeping.
        """
        if self.admission == "priority":
            return sorted(self.waiting, key=lambda r: (r.priority, r.seq))
        return list(self.waiting)

    def _admit(self):
        """Prefill waiting requests into free slots, scanning the queue in
        ``_admission_order``.

        A request that finishes at its prefill token (EOS, or
        ``max_new_tokens == 1``) leaves its slot free, so the same slot is
        retried until it is actually occupied or the queue drains — skipping
        ahead would idle the slot for a whole engine tick per short request.
        """
        for slot in range(self.max_batch):
            while self.slots[slot] is None and self._slot_usable(slot):
                queue = self._admission_order()
                if not queue or not self._admittable(slot, queue[0]):
                    break
                req = queue[0]
                self.waiting.remove(req)
                if req.admit_time is None:
                    req.admit_time = self.clock()
                tok, payload = self._prefill_into_slot(slot, req)
                if req.prefill_done_time is None:
                    req.prefill_done_time = self.clock()
                req.generated.append(tok)
                if req.first_token_time is None:
                    req.first_token_time = self.clock()
                if tok == req.eos_id or len(req.generated) >= req.max_new_tokens:
                    req.finish_time = self.clock()
                    self.finished.append(req)
                    self._release_slot(slot)
                    continue  # slot still free: offer it to the next waiter
                self._install_slot(slot, payload)
                self.slots[slot] = req
                self._next_token[slot, 0] = tok
                self._active[slot] = True

    def _prefill_into_slot(self, slot: int, req: Request):
        raise NotImplementedError

    def _install_slot(self, slot: int, payload):
        raise NotImplementedError

    def _release_slot(self, slot: int):
        """Hook: a request left this slot (paged engines free its pages)."""

    def _harvest(self, next_ids: np.ndarray, slot_range=None) -> int:
        """Record one decoded token per active slot; retire finished slots.
        ``next_ids`` is indexed by absolute slot id."""
        n_emitted = 0
        for slot in slot_range if slot_range is not None else range(self.max_batch):
            req = self.slots[slot]
            if req is None:
                continue
            tok = int(next_ids[slot])
            req.generated.append(tok)
            n_emitted += 1
            self._next_token[slot, 0] = tok
            if tok == req.eos_id or len(req.generated) >= req.max_new_tokens:
                req.finish_time = self.clock()
                self.finished.append(req)
                self.slots[slot] = None
                self._active[slot] = False
                self._release_slot(slot)
        return n_emitted

    def _harvest_tokens(self, slot: int, tokens) -> int:
        """Multi-token variant of :meth:`_harvest` for one slot: commit a
        speculative round's accepted tokens in order.  EOS or the
        ``max_new_tokens`` budget can land mid-commit — the remaining
        accepted tokens are discarded (non-speculative decode would never
        have produced them) and the slot retires exactly as in
        :meth:`_harvest`."""
        req = self.slots[slot]
        if req is None or not tokens:
            return 0
        n_emitted = 0
        for tok in tokens:
            tok = int(tok)
            req.generated.append(tok)
            n_emitted += 1
            self._next_token[slot, 0] = tok
            if tok == req.eos_id or len(req.generated) >= req.max_new_tokens:
                req.finish_time = self.clock()
                self.finished.append(req)
                self.slots[slot] = None
                self._active[slot] = False
                self._release_slot(slot)
                break
        return n_emitted

    # -- stepping ------------------------------------------------------------

    def step(self) -> int:
        raise NotImplementedError

    def _progress_sig(self) -> tuple:
        """Progress signature for the livelock guard: admission, decode,
        and completion all move it.  Subclasses extend with their own
        monotone counters (prefill chunks, transfers, retries) so slow but
        real work — a prefetch crawling over a degraded link — never reads
        as a stall."""
        gen = sum(
            len(r.generated) for r in self.slots if r is not None
        )
        return (
            len(self.finished), len(self.waiting),
            int(self._active.sum()), gen,
        )

    def stall_diagnostic(self) -> str:
        """Queue/slot snapshot for the livelock guard's error message
        (``.`` free, ``i`` installed-inactive, ``A`` actively decoding)."""
        slots = "".join(
            "." if r is None else ("A" if self._active[i] else "i")
            for i, r in enumerate(self.slots)
        )
        return (
            f"waiting={len(self.waiting)} finished={len(self.finished)} "
            f"slots=[{slots}]"
        )

    def run(self, max_steps: int = 10_000):
        """Run until all submitted requests finish.  A livelock guard
        watches the progress signature: ``stall_limit`` consecutive busy
        ticks in which nothing was admitted, decoded, transferred, or
        retried raise loudly with a queue/slot diagnostic instead of
        silently spinning to ``max_steps`` and returning partial results
        that look like success."""
        from repro.serving.faults import StallGuard

        guard = StallGuard(self.stall_limit)
        for _ in range(max_steps):
            if not self.busy():
                break
            self.step()
            guard.note(self._progress_sig(), self.stall_diagnostic)
        return self.finished
