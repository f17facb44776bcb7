"""Paged KV-cache subsystem + chunked pipelined prefill.

Covers the tentpole invariants:
  (a) PagePool alloc/free bookkeeping: reservations, ring reuse, double-free
      detection, exhaustion, defrag compaction;
  (b) greedy decode token parity paged-vs-dense (the pre-refactor
      ``Model.prefill``/``decode_step`` path) at splits 0 / mid / R;
  (c) chunked prefill is token-identical to whole-prompt prefill;
  (d) a skewed-length batch allocates measurably fewer KV bytes than the
      dense ``max_batch x max_len`` layout;
  (e) no page leaks across request finish + replan re-split (pages move
      between tier pools by table-aware permutation);
  (f) chunked prefill never stalls in-flight decode groups (admission is a
      pipeline stage, visible as StageTimeline occupancy);
  (g) the number of compiled stage traces is bounded by chunk/group shapes,
      not by distinct prompt lengths;
  (h) download metering charges only active slots (regression);
  (i) micro-batch groups are equal-sized (padded batch), one decode trace;
  (j) the stage programs do not consume the pools they are given: after a
      call the storage passed in is still readable and unchanged, the
      engines rebind the returned storage, and warm-up, spill/restore,
      defrag and a replan re-split all serve the dense oracle's greedy
      tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.hardware import PROFILES, DeviceProfile
from repro.models import kvcache
from repro.models.kvcache import PagePool
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.fleet import FleetServingEngine
from repro.serving.stream import EndCloudServingEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = smoke_config(get_config("tinyllama-1.1b")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def tiny_model_f32():
    """Float32 twin for dense-oracle parity: the dense path prefills via
    flash attention (normalizes as ``acc / l``) while the chunked path
    normalizes as ``softmax(s) @ v`` — same math, different low-bit
    rounding, so bf16 greedy argmax can tie-break differently.  In f32 the
    gap is ~1e-7 relative and the comparison is deterministic."""
    cfg = (
        smoke_config(get_config("tinyllama-1.1b"))
        .replace(num_layers=4, dtype="float32")
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(n, seed=0, lo=4, hi=16):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 500, size=int(rng.integers(lo, hi))).astype(np.int32)
        for _ in range(n)
    ]


def _dense_oracle(model, params, prompts, max_new_tokens, max_len=64):
    """Greedy tokens via the pre-refactor dense ring-buffer cache path."""
    out = {}
    for i, prompt in enumerate(prompts):
        lg, cache = model.prefill(
            params, {"tokens": jnp.asarray(prompt)[None]}, max_len=max_len
        )
        toks = [int(jnp.argmax(lg[0]))]
        for _ in range(max_new_tokens - 1):
            lg, cache = model.decode_step(
                params, jnp.asarray([[toks[-1]]], jnp.int32), cache
            )
            toks.append(int(jnp.argmax(lg[0])))
        out[i] = toks
    return out


# ------------------------------------------------------------- (a) PagePool


def test_page_pool_invariants():
    pool = PagePool(num_pages=8, page_size=4, pages_per_slot=4, n_slots=3)
    assert pool.pages_available == 8

    pool.reserve(0, kvcache.pages_needed(10, 4, 4))  # 3 pages
    pool.map_range(0, 0, 7)
    assert pool.pages_in_use == 2 and pool.pages_reserved == 1
    pool.append(0, 8)
    assert pool.pages_in_use == 3
    with pytest.raises(ValueError, match="reservation"):
        pool.append(0, 12)  # beyond its reservation
    # ring reuse: wrapping positions revisit mapped entries, no new pages
    pool.free(0)
    pool.reserve(0, 4)
    for pos in range(40):
        pool.append(0, pos)
    assert pool.pages_in_use == 4

    with pytest.raises(ValueError, match="already holds"):
        pool.reserve(0, 1)
    pool.reserve(1, 4)
    assert not pool.can_reserve(1)  # 8 pages, 8 spoken for
    with pytest.raises(ValueError, match="exhausted"):
        pool.reserve(2, 1)

    pool.free(0)
    with pytest.raises(ValueError, match="double free"):
        pool.free(0)
    assert pool.pages_in_use == 0 and pool.pages_available == 4

    # defrag: mapped pages compact to the lowest physical rows and the
    # permutation is a bijection fixing the garbage row
    pool.map_range(1, 0, 16)
    before = {
        (1, e): pool.table[1, e] for e in range(4)
    }
    perm = pool.defrag()
    assert sorted(perm[:-1].tolist()) == list(range(8))
    assert perm[-1] == 8
    assert sorted(pool.table[1].tolist()) == [0, 1, 2, 3]
    for e in range(4):
        assert perm[pool.table[1, e]] == before[(1, e)]


def test_page_perm_requires_lockstep():
    a = PagePool(4, 2, 2, n_slots=1)
    b = PagePool(4, 2, 2, n_slots=1)
    a.reserve(0, 2)
    b.reserve(0, 2)
    a.map_range(0, 0, 4)
    b.map_range(0, 0, 2)  # one entry behind
    with pytest.raises(ValueError, match="lockstep"):
        kvcache.page_perm(a.table, b.table, 4, 4)


# ------------------------------------------- (b) paged-vs-dense token parity


@pytest.mark.parametrize("split", [0, 2, 4])
def test_paged_matches_dense_oracle(tiny_model_f32, split):
    model, params = tiny_model_f32
    prompts = _prompts(6)
    want = _dense_oracle(model, params, prompts, max_new_tokens=8)
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, force_split=split, prefill_chunk=8,
    )
    reqs = [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 6
    assert {r.request_id: r.generated for r in reqs} == want


def test_sliding_window_chunked_prefill_matches_dense_oracle():
    """Regression: with a sliding window smaller than max_len the ring can
    wrap DURING prefill — a chunk's own writes must never evict keys still
    inside an early chunk query's window.  page_geometry adds one chunk of
    ring headroom for exactly this; greedy tokens must match the dense
    whole-prompt path (f32: the two prefill paths round differently in
    low-order bits)."""
    cfg = (
        smoke_config(get_config("tinyllama-1.1b"))
        .replace(num_layers=2, dtype="float32", sliding_window=24)
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    # prompts well past the window so prefill wraps the ring
    prompts = [rng.integers(0, 500, size=s).astype(np.int32)
               for s in (40, 55, 48)]
    want = _dense_oracle(model, params, prompts, max_new_tokens=6, max_len=64)
    eng = ServingEngine(model, params, max_batch=2, max_len=64,
                        prefill_chunk=16)
    reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert {r.request_id: r.generated for r in reqs} == want


def test_over_capacity_request_fails_at_submit(tiny_model):
    """Regression: a request needing more pages than the pool holds could
    never be admitted; it must fail loudly at submit instead of blocking
    the FIFO queue forever."""
    model, params = tiny_model
    eng = ServingEngine(model, params, max_batch=4, max_len=64, kv_pages=2,
                        page_size=16)
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(Request(0, np.arange(40).astype(np.int32),
                           max_new_tokens=16))
    assert not eng.waiting
    seng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, force_split=2, kv_pages=2, page_size=16,
    )
    with pytest.raises(ValueError, match="KV pages"):
        seng.submit(Request(0, np.arange(40).astype(np.int32),
                            max_new_tokens=16))
    # a fitting request still serves
    seng.submit(Request(1, np.arange(12).astype(np.int32), max_new_tokens=4))
    done = seng.run()
    assert len(done) == 1 and len(done[0].generated) == 4


# --------------------------------- (c) chunked == whole-prompt prefill parity


def test_chunked_prefill_matches_whole_prompt(tiny_model):
    model, params = tiny_model
    prompts = _prompts(6, seed=2, lo=8, hi=24)
    tokens = {}
    for chunk in (4, 32):  # 32 >= every prompt: single-chunk == whole-prompt
        eng = ServingEngine(
            model, params, max_batch=4, max_len=64, prefill_chunk=chunk
        )
        reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        tokens[chunk] = {r.request_id: r.generated for r in reqs}
    assert tokens[4] == tokens[32]


# ------------------------------------------------- (d) skewed-batch KV bytes


def test_skewed_batch_uses_fewer_kv_bytes(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(5)
    long_prompt = rng.integers(0, 500, size=100).astype(np.int32)
    shorts = _prompts(7, seed=6, lo=6, hi=10)
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=8, max_len=128, force_split=2,
    )
    eng.submit(Request(0, long_prompt, max_new_tokens=8))
    for i, p in enumerate(shorts):
        eng.submit(Request(1 + i, p, max_new_tokens=8))
    done = eng.run()
    assert len(done) == 8
    m = eng.metrics()
    # 1 long + 7 short: peak paged footprint must be well under the dense
    # max_batch x max_len layout (the long request pays for its pages, the
    # short ones only for theirs)
    assert m["kv_bytes_peak"] > 0
    assert m["kv_bytes_peak"] <= m["kv_bytes_dense_equiv"] / 2, m
    # every page returned once the batch drained
    assert m["kv_pages_in_use"] == 0


# --------------------------------------- (e) no leaks across finish + replan


def test_no_page_leak_across_finish_and_replan(tiny_model):
    model, params = tiny_model
    prompts = _prompts(6)
    # same-arithmetic reference: the paged single-tier engine (greedy decode
    # across a replan re-split must be bit-identical to a split-free run)
    ref = ServingEngine(model, params, max_batch=4, max_len=64,
                        prefill_chunk=8)
    for i, p in enumerate(prompts):
        ref.submit(Request(i, p, max_new_tokens=8))
    ref.run()
    want = {r.request_id: r.generated for r in ref.finished}
    weak_end = DeviceProfile("weak-end", peak_gflops=2.0, mem_gb=8.0,
                             mem_bw_gbs=50.0, net_gbps=0.3)
    eng = EndCloudServingEngine(
        model, params,
        end_profile=weak_end, cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, force_split=model.cfg.block_repeat,
        prefill_chunk=8,
    )
    reqs = [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    eng.observe_bandwidth(weak_end.net_gbps)  # forces the off-optimal replan
    eng.run()
    assert len(eng.replan_events) >= 1
    assert eng.replan_events[0]["new_split"] != model.cfg.block_repeat
    # token parity held across the re-split page move + defrag
    assert {r.request_id: r.generated for r in reqs} == want
    # and every page of both tier pools came back
    assert eng.end_pool.pages_in_use == 0
    assert eng.cloud_pool.pages_in_use == 0
    assert eng.end_pool.pages_reserved == 0
    assert eng.cloud_pool.pages_reserved == 0


def test_fleet_shared_cloud_pool_drains(tiny_model):
    model, params = tiny_model
    fleet = FleetServingEngine(
        model, params,
        end_profiles=[PROFILES["a100"], PROFILES["a100"]],
        cloud_profile=PROFILES["a100"],
        cloud_servers=1, max_batch=2, max_len=64,
    )
    for i, p in enumerate(_prompts(6, seed=9)):
        fleet.submit(Request(i, p, max_new_tokens=6))
    done = fleet.run()
    assert len(done) == 6
    m = fleet.metrics()
    assert m["kv_pages_in_use"] == 0
    assert fleet.cloud_pool.pages_in_use == 0
    assert m["kv_bytes_peak"] > 0
    # both lanes drew their cloud pages from the one shared pool
    assert fleet.lanes[0].cloud_pool is fleet.cloud_pool
    assert fleet.lanes[1].cloud_pool is fleet.cloud_pool
    assert fleet.lanes[0]._cloud_base != fleet.lanes[1]._cloud_base


# ------------------------------------------------ (f) no stop-the-world admit


def test_long_prompt_prefill_does_not_stall_decode(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(11)
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=128, force_split=2, prefill_chunk=8,
    )
    # warm in-flight generations in both groups, one slot left free for
    # the long prompt (its prefill must interleave with LIVE decodes)
    for i, p in enumerate(_prompts(3, seed=10)):
        eng.submit(Request(i, p, max_new_tokens=64))
    for _ in range(4):
        eng.step()
    counts_before = {r.request_id: len(r.generated) for r in eng.slots if r}
    assert counts_before

    def emitted_total():
        live = sum(len(r.generated) for r in eng.slots if r)
        return live + sum(len(r.generated) for r in eng.finished)

    long_req = Request(99, rng.integers(0, 500, 96).astype(np.int32),
                       max_new_tokens=4)
    eng.submit(long_req)
    chunks_seen = 0
    stalled_ticks = 0
    while 99 in {j.req.request_id for j in eng._jobs.values()} or eng.waiting:
        before = emitted_total()
        eng.step()
        if emitted_total() == before:
            stalled_ticks += 1
        chunks_seen = eng.n_prefill_chunks
    # 96-token prompt at chunk 8 = 12 chunks, streamed over >= 12 ticks
    assert chunks_seen >= 12
    # in-flight decode kept emitting on every tick of the prefill
    assert stalled_ticks == 0
    # prefill chunks are visible as StageTimeline occupancy alongside decode
    assert eng._prefill_busy["end"] > 0 and eng._prefill_busy["cloud"] > 0
    assert eng.timeline.busy_s["end"] == pytest.approx(
        eng._stage_busy["end"] + eng._prefill_busy["end"]
    )
    eng.run()
    assert long_req.done and len(long_req.generated) == 4


# -------------------------------------------------- (g) bounded trace counts


def test_trace_count_bounded_by_shapes_not_prompt_lengths(tiny_model):
    model, params = tiny_model
    # 12 requests covering 12 distinct prompt lengths
    prompts = [np.arange(4 + i).astype(np.int32) % 500 for i in range(12)]
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, force_split=2, prefill_chunk=8,
    )
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=4))
    eng.run()
    counts = eng.stage_trace_counts()
    # one decode trace per tier (single group shape) and one chunk trace per
    # tier (single chunk shape) — NOT one per distinct prompt length
    assert counts == {
        "end_step": 1,
        "cloud_step": 1,
        "end_prefill_chunk": 1,
        "cloud_prefill_chunk": 1,
    }, counts

    single = ServingEngine(model, params, max_batch=4, max_len=64,
                           prefill_chunk=8)
    for i, p in enumerate(prompts):
        single.submit(Request(i, p, max_new_tokens=4))
    single.run()
    assert single.stage_trace_counts() == {"decode": 1, "prefill_chunk": 1}


# ------------------------------------------- (h) download metering regression


def test_record_down_meters_only_active_slots(tiny_model):
    """A half-empty group must not be charged token-id downlink bytes for
    its inactive slots: every generated token crosses the wire down exactly
    once, so bytes_down == 4 * total tokens."""
    model, params = tiny_model
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, n_groups=2, max_len=64, force_split=2,
    )
    # one request -> group 0 runs half-empty, group 1 never runs
    req = Request(0, np.arange(8).astype(np.int32), max_new_tokens=10)
    eng.submit(req)
    eng.run()
    total_tokens = len(req.generated)
    assert total_tokens == 10
    assert eng.link.bytes_down == 4 * total_tokens


# ------------------------------------------------- (i) equal-sized groups


def test_groups_are_equal_sized_with_padding(tiny_model):
    model, params = tiny_model
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=5, n_groups=2, max_len=64, force_split=2,
    )
    sizes = {ge - gs for gs, ge in eng._group_slices}
    assert sizes == {3}  # ceil(5/2), padded batch = 6
    assert eng.max_batch == 6 and eng.request_capacity == 5
    assert not eng._slot_usable(5)  # the padding slot never admits
    reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(_prompts(7))]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 7
    assert eng.slots[5] is None
    # equal groups -> exactly one compiled decode trace per tier
    assert eng.stage_trace_counts()["end_step"] == 1


# ------------------------------------------ (j) pools are not consumed


@pytest.mark.parametrize("kind", ["end_cloud", "single"])
def test_served_tick_leaves_the_previous_pools_unchanged(tiny_model, kind):
    """A served tick's stage calls return new storage and leave the storage
    they were given as it was: the buffers the engine held before the tick
    are live and unchanged after it, the engine holds the ones the calls
    returned, which hold the tick's tokens, and it decodes on to the end."""
    model, params = tiny_model
    if kind == "end_cloud":
        eng = EndCloudServingEngine(
            model, params,
            end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
            max_batch=2, max_len=64, force_split=2, prefill_chunk=8,
        )

        def pools():
            return jax.tree.leaves((eng._end_pages, eng._cloud_pages))
    else:
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            prefill_chunk=8)

        def pools():
            return jax.tree.leaves(eng.pages)
    reqs = [Request(i, p, max_new_tokens=6)
            for i, p in enumerate(_prompts(2, seed=12))]
    for r in reqs:
        eng.submit(r)
    eng.step()
    before = pools()
    kept = [np.asarray(leaf) for leaf in before]
    eng.step()
    after = pools()
    assert not any(leaf.is_deleted() for leaf in before)
    for leaf, want in zip(before, kept):
        np.testing.assert_array_equal(np.asarray(leaf), want)
    assert any(not np.array_equal(np.asarray(new), old)
               for new, old in zip(after, kept))
    eng.run()
    assert all(r.done and len(r.generated) == 6 for r in reqs)


def test_pools_through_spill_defrag_and_replan_match_dense_oracle(
    tiny_model_f32,
):
    """Every path that rewrites a tier's storage outside a stage call —
    warm-up, a preemption's spill and restore, and a replan's block re-split
    with its defrag — keeps the stacked lane-dense pools consistent: greedy
    tokens equal the dense oracle's (f32) and no page leaks."""
    model, params = tiny_model_f32
    prompts = _prompts(3, seed=13, lo=8, hi=14)
    want = _dense_oracle(model, params, prompts, max_new_tokens=10)
    weak_end = DeviceProfile("weak-end", peak_gflops=2.0, mem_gb=8.0,
                             mem_bw_gbs=50.0, net_gbps=0.3)
    eng = EndCloudServingEngine(
        model, params,
        end_profile=weak_end, cloud_profile=PROFILES["a100"],
        max_batch=2, max_len=64, force_split=model.cfg.block_repeat,
        prefill_chunk=8, admission="priority", preemption=True,
    )
    low = [Request(i, prompts[i], max_new_tokens=10, priority=2)
           for i in (0, 1)]
    urgent = Request(2, prompts[2], max_new_tokens=10, priority=0)
    for r in low:
        eng.submit(r)
    for _ in range(200):
        eng.step()
        if all(len(r.generated) >= 3 for r in low):
            break
    eng.submit(urgent)
    eng.step()
    assert eng.n_preemptions == 1
    eng.observe_bandwidth(weak_end.net_gbps)  # forces the off-optimal replan
    eng.run()
    assert eng.n_preempt_restores == 1
    assert eng.replan_events and eng.split != model.cfg.block_repeat
    got = {r.request_id: r.generated for r in low + [urgent]}
    assert got == want
    assert eng.end_pool.pages_in_use == 0 == eng.cloud_pool.pages_in_use
