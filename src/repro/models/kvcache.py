"""Decode caches: dense ring buffers and the paged KV subsystem.

**Dense caches** (the original layout, still used by ``Model.prefill`` /
``Model.decode_step`` and the dry-run input specs): per pattern-position
caches are stacked along a leading ``block_repeat`` axis so the decode step
can ``lax.scan`` over blocks.  Attention caches are ring buffers: slot
``p % W`` holds position ``p``, so a full-attention cache sized W behaves
exactly like sliding-window attention with window W once it wraps.

**Paged caches** (what the serving engines allocate): instead of a dense
``[R, B, W, KV, hd]`` ring per slot, a tier owns one shared :class:`PagePool`
of ``num_pages`` fixed-size pages — leaves are ``[R, P+1, page_size,
KV*hd]`` (the extra last row is the *garbage page* that absorbs writes
routed away from unmapped or inactive slots; a token's heads lie side by
side in one lane-dense row) — plus a per-slot *page table*
``[pages_per_slot]`` of physical page indices.  Ring semantics are
preserved at page granularity: position ``p`` lives at table entry
``(p // page_size) % pages_per_slot``, offset ``p % page_size``, so the
gathered per-slot view is *exactly* the dense ring buffer of capacity
``pages_per_slot * page_size`` and the existing ring position math
(:func:`ring_key_positions`) applies unchanged.  A sliding window is just a
bounded page list; ring wrap reuses the slot's own pages in place.

The pool's allocator is host-side (NumPy bookkeeping between engine ticks);
the jitted stage functions take the device page table as a runtime argument,
so compiled traces depend only on chunk/group *shapes*, never on prompt
lengths or allocation state.  The stage functions take the whole stacked
storage and write it in place: each layer scatters its tokens at
``[block, page, offset]`` and attention reads block ``block`` of the stack
directly, so no layer of a pool is sliced out or copied back.  The serving
engines do not donate the storage, so each stage program copies every pool
leaf once on entry and updates that copy.

``lengths`` is per-slot (continuous batching: every request in the batch has
its own offset).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ssm import ssm_dims


def attn_cache_len(cfg, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> Dict:
    """Allocate an empty decode cache pytree (zeros; also usable as a
    ShapeDtypeStruct template via jax.eval_shape)."""
    R = cfg.block_repeat
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    blocks: Dict[str, Dict] = {}
    for i, spec in enumerate(cfg.layer_pattern):
        if spec.kind == "attn":
            W = attn_cache_len(cfg, max_len)
            c = {
                "k": jnp.zeros((R, batch, W, KV, hd), dtype),
                "v": jnp.zeros((R, batch, W, KV, hd), dtype),
            }
            if spec.cross_attn:
                c["xk"] = jnp.zeros((R, batch, cfg.encoder_seq_len, KV, hd), dtype)
                c["xv"] = jnp.zeros((R, batch, cfg.encoder_seq_len, KV, hd), dtype)
        else:
            s = cfg.ssm
            d_in, H, conv_ch = ssm_dims(cfg)
            gn = s.n_groups * s.d_state
            c = {
                "conv_x": jnp.zeros((R, batch, s.d_conv - 1, d_in), dtype),
                "conv_bc": jnp.zeros((R, batch, s.d_conv - 1, 2 * gn), dtype),
                "ssm": jnp.zeros((R, batch, H, s.head_dim, s.d_state), jnp.float32),
            }
        blocks[f"pos{i}"] = c
    return {
        "blocks": blocks,
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def split_cache(cache: Dict, split: int) -> tuple:
    """Split a stacked block cache by layer range: blocks ``[0, split)`` for
    the end tier, ``[split, R)`` for the cloud tier (the streaming end-cloud
    engine holds one sub-cache per tier).  Each side gets its own ``lengths``
    vector — the tiers advance them independently as the pipeline steps.
    """
    end = {
        "blocks": jax.tree.map(lambda l: l[:split], cache["blocks"]),
        "lengths": cache["lengths"],
    }
    cloud = {
        "blocks": jax.tree.map(lambda l: l[split:], cache["blocks"]),
        "lengths": cache["lengths"],
    }
    return end, cloud


def merge_cache(end_cache: Dict, cloud_cache: Dict) -> Dict:
    """Inverse of :func:`split_cache`: re-stack the per-tier block caches
    along the leading block axis (used at replan boundaries, when both tiers
    are at the same ``lengths``)."""
    blocks = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b], axis=0),
        end_cache["blocks"],
        cloud_cache["blocks"],
    )
    return {"blocks": blocks, "lengths": end_cache["lengths"]}


def install_slot(batch_cache: Dict, slot: int, one_cache: Dict) -> Dict:
    """Copy a single-request cache (batch dim 1) into slot ``slot`` of a
    batched cache.  Block leaves are [R, B, W, ...]; the ring-buffer axis
    (dim 2) is padded/truncated to the destination window."""

    def copy_leaf(batch_leaf, one_leaf):
        pad = batch_leaf.shape[2] - one_leaf.shape[2] if batch_leaf.ndim > 2 else 0
        src = one_leaf
        if pad > 0:
            width = [(0, 0)] * src.ndim
            width[2] = (0, pad)
            src = jnp.pad(src, width)
        elif pad < 0:
            src = jax.lax.slice_in_dim(src, 0, batch_leaf.shape[2], axis=2)
        return batch_leaf.at[:, slot].set(src[:, 0])

    return {
        "blocks": jax.tree.map(copy_leaf, batch_cache["blocks"], one_cache["blocks"]),
        "lengths": batch_cache["lengths"].at[slot].set(one_cache["lengths"][0]),
    }


def ring_key_positions(lengths: jax.Array, W: int) -> jax.Array:
    """Position held by each ring slot AFTER the token at ``lengths`` (the
    current query) has been written.  lengths: [B] -> [B, W]."""
    s = jnp.arange(W)[None, :]
    ln = lengths[:, None]
    return ln - jnp.mod(ln - s, W)


def ring_write(kcache: jax.Array, vcache: jax.Array, k, v, lengths):
    """Write one new token's k/v ([B, 1, KV, hd]) at slot lengths % W."""
    W = kcache.shape[1]
    b = jnp.arange(kcache.shape[0])
    slot = jnp.mod(lengths, W)
    kcache = kcache.at[b, slot].set(k[:, 0].astype(kcache.dtype))
    vcache = vcache.at[b, slot].set(v[:, 0].astype(vcache.dtype))
    return kcache, vcache


def prefill_write(kcache: jax.Array, vcache: jax.Array, k, v):
    """Write a full prefix [B, S, KV, hd] into a fresh cache (ring layout).

    If S > W only the last W tokens are kept; their slots are pos % W.
    """
    B, S = k.shape[0], k.shape[1]
    W = kcache.shape[1]
    if S >= W:
        tail_k, tail_v = k[:, S - W :], v[:, S - W :]
        pos = jnp.arange(S - W, S)
        slot = jnp.mod(pos, W)
        kcache = kcache.at[:, slot].set(tail_k.astype(kcache.dtype))
        vcache = vcache.at[:, slot].set(tail_v.astype(vcache.dtype))
    else:
        kcache = jax.lax.dynamic_update_slice_in_dim(
            kcache, k.astype(kcache.dtype), 0, axis=1
        )
        vcache = jax.lax.dynamic_update_slice_in_dim(
            vcache, v.astype(vcache.dtype), 0, axis=1
        )
    return kcache, vcache


# ---------------------------------------------------------------------------
# Paged KV subsystem
# ---------------------------------------------------------------------------


def pattern_is_pageable(cfg) -> bool:
    """Paged caches cover self-attention KV only: every layer must be a
    non-cross attention layer.  SSM states are O(1) per slot (nothing to
    page) but chunked prefill cannot resume an SSM scan mid-sequence, so
    hybrid patterns stay on the dense path."""
    return all(
        spec.kind == "attn" and not spec.cross_attn for spec in cfg.layer_pattern
    )


def page_geometry(cfg, max_len: int, page_size: int,
                  chunk_headroom: int = 0) -> Tuple[int, int]:
    """(pages_per_slot, ring_capacity_tokens) for a slot's bounded page
    list.  The ring capacity is ``attn_cache_len`` rounded up to whole
    pages, so the gathered per-slot view is a dense ring buffer of at least
    the window the dense layout would have used.

    ``chunk_headroom`` (the engine's prefill chunk size) matters only when
    the ring can actually wrap — a sliding window smaller than ``max_len``:
    chunked prefill writes a whole chunk before its queries attend, so
    without ``ring >= window + chunk - 1`` a chunk's own writes could evict
    keys still inside an early query's attention window.  The extra ring
    tokens are harmless for decode (positions past the window stay
    masked)."""
    W = attn_cache_len(cfg, max_len)
    if W < max_len and chunk_headroom > 1:
        W += chunk_headroom - 1
    pps = -(-W // page_size)  # ceil
    return pps, pps * page_size


def pages_needed(n_tokens: int, page_size: int, pages_per_slot: int) -> int:
    """Distinct table entries positions ``[0, n_tokens)`` ever touch (entry
    indices cycle mod ``pages_per_slot``, so a long request plateaus at the
    ring bound — sliding windows reuse their own pages in place)."""
    return min(pages_per_slot, -(-n_tokens // page_size))


class PagePool:
    """Host-side page allocator for one tier's shared KV page pool.

    Physical pages ``0..num_pages-1`` index the second axis of the tier's
    storage leaves (``[R, num_pages+1, page_size, KV*hd]``; row
    ``num_pages`` is the garbage page and is never allocated).  Per-slot
    page tables map ring entries to physical pages; ``-1`` = unmapped.

    Admission *reserves* a slot's worst-case page count up front (so decode
    can never run out of pages mid-stream — there is no preemption), then
    maps pages lazily as prefill chunks / decode steps first touch each
    ring entry.  ``free`` returns a finished slot's pages; ``defrag``
    compacts mapped pages to the lowest physical indices and returns the
    storage-row permutation to apply device-side.

    In a fleet, one pool instance can be shared across lanes for the cloud
    tier: each lane registers its slot block via :meth:`add_slots`, so page
    accounting (and therefore admission) is fleet-wide.
    """

    def __init__(self, num_pages: int, page_size: int, pages_per_slot: int,
                 n_slots: int = 0):
        if num_pages < 1:
            raise ValueError(f"num_pages={num_pages}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.table = np.full((n_slots, pages_per_slot), -1, np.int32)
        # LIFO free list, seeded so pops hand out low indices first
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._reserved = np.zeros((n_slots,), np.int64)
        self._mapped = np.zeros((n_slots,), np.int64)
        self.peak_in_use = 0

    # -- capacity accounting --------------------------------------------------

    @property
    def garbage_page(self) -> int:
        return self.num_pages

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def pages_reserved(self) -> int:
        """Pages promised to admitted slots but not yet mapped."""
        return int(self._reserved.sum() - self._mapped.sum())

    @property
    def pages_available(self) -> int:
        """Pages a new reservation may claim."""
        return len(self._free) - self.pages_reserved

    @property
    def utilization(self) -> float:
        return self.pages_in_use / self.num_pages

    def mapped_for(self, slots) -> int:
        """Pages mapped by a slot subset (a lane's share of a shared pool)."""
        return int(self._mapped[np.asarray(slots)].sum())

    def add_slots(self, n: int) -> int:
        """Register ``n`` more slots (fleet lanes sharing a cloud pool);
        returns the base slot id of the new block."""
        base = self.table.shape[0]
        self.table = np.concatenate(
            [self.table, np.full((n, self.pages_per_slot), -1, np.int32)]
        )
        self._reserved = np.concatenate([self._reserved, np.zeros(n, np.int64)])
        self._mapped = np.concatenate([self._mapped, np.zeros(n, np.int64)])
        return base

    # -- slot lifecycle -------------------------------------------------------

    def can_reserve(self, n_pages: int) -> bool:
        return self.pages_available >= n_pages

    def reserve(self, slot: int, n_pages: int):
        if self._reserved[slot]:
            raise ValueError(f"slot {slot} already holds a reservation")
        if n_pages > self.pages_per_slot:
            raise ValueError(
                f"reservation {n_pages} exceeds pages_per_slot="
                f"{self.pages_per_slot}"
            )
        if not self.can_reserve(n_pages):
            raise ValueError(
                f"pool exhausted: want {n_pages}, available {self.pages_available}"
            )
        self._reserved[slot] = n_pages

    def _map_entry(self, slot: int, entry: int):
        if self.table[slot, entry] >= 0:
            return  # ring reuse: the entry keeps its page across wraps
        if self._mapped[slot] >= self._reserved[slot]:
            raise ValueError(
                f"slot {slot}: mapping beyond its reservation "
                f"({self._reserved[slot]} pages)"
            )
        self.table[slot, entry] = self._free.pop()
        self._mapped[slot] += 1
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)

    def map_range(self, slot: int, start_pos: int, end_pos: int):
        """Map every ring entry positions ``[start_pos, end_pos)`` touch."""
        if end_pos <= start_pos:
            return
        for pi in range(start_pos // self.page_size,
                        (end_pos - 1) // self.page_size + 1):
            self._map_entry(slot, pi % self.pages_per_slot)

    def append(self, slot: int, pos: int):
        """Ensure the entry for position ``pos`` is mapped (decode write)."""
        self._map_entry(slot, (pos // self.page_size) % self.pages_per_slot)

    # -- speculative decode: provisional maps / rollback ----------------------

    def map_tokens(self, slot: int, start_pos: int, end_pos: int) -> List[int]:
        """Map every ring entry positions ``[start_pos, end_pos)`` touch and
        return the entries that were *newly* mapped by this call.

        Speculative decode maps a draft chunk's pages provisionally through
        here; on a mid-chunk rejection the caller hands the returned entries
        (minus any the accepted prefix still needs) to :meth:`rollback`.
        Ring-reused entries — already mapped from an earlier wrap — are not
        returned: they were never provisional and must survive a rollback."""
        new_entries: List[int] = []
        if end_pos > start_pos:
            for pi in range(start_pos // self.page_size,
                            (end_pos - 1) // self.page_size + 1):
                entry = pi % self.pages_per_slot
                if self.table[slot, entry] < 0:
                    self._map_entry(slot, entry)
                    new_entries.append(entry)
        return new_entries

    def rollback(self, slot: int, entries) -> None:
        """Unmap provisionally-mapped ``entries`` (from :meth:`map_tokens`),
        returning their physical pages to the free list.  No data moves —
        rejected draft tokens only ever lived in lazily-mapped pages, so
        rollback is pure table surgery (the inverse of ``_map_entry``)."""
        for e in entries:
            e = int(e)
            if self.table[slot, e] < 0:
                raise ValueError(f"slot {slot}: rollback of unmapped entry {e}")
            self._free.append(int(self.table[slot, e]))
            self.table[slot, e] = -1
            self._mapped[slot] -= 1

    def free(self, slot: int):
        if not self._reserved[slot]:
            raise ValueError(f"double free of slot {slot}")
        for e in range(self.pages_per_slot):
            if self.table[slot, e] >= 0:
                self._free.append(int(self.table[slot, e]))
                self.table[slot, e] = -1
        self._reserved[slot] = 0
        self._mapped[slot] = 0

    def reserved_pages(self, slot: int) -> int:
        """Pages this slot's reservation holds (0 = no reservation)."""
        return int(self._reserved[slot])

    # -- preemption: spill / restore ------------------------------------------

    def spill_slot(self, slot: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Evict a live slot for preemption.

        Returns ``(entries, phys, n_reserved)``: the slot's mapped table
        entries, the physical page row each entry occupied, and the page
        count of its reservation.  The slot's pages go back to the free
        list — the caller must copy the storage rows at ``phys`` *before*
        anything else maps (and writes) those pages, then hand
        ``(entries, n_reserved)`` back to :meth:`restore_slot` at
        re-admission."""
        entries = np.nonzero(self.table[slot] >= 0)[0].astype(np.int64)
        phys = self.table[slot, entries].astype(np.int64).copy()
        n_reserved = int(self._reserved[slot])
        self.free(slot)
        return entries, phys, n_reserved

    def restore_slot(self, slot: int, entries: np.ndarray,
                     n_pages: int) -> np.ndarray:
        """Re-admit a spilled slot: reserve ``n_pages`` (the original
        worst-case reservation, so decode still can never run out
        mid-stream) and map exactly the spilled ``entries``.  Returns the
        entries' new physical rows — the caller scatters the saved page
        data there; ring-entry indices are placement-invariant, so reads
        through the rebuilt table see the exact pre-spill cache."""
        self.reserve(slot, n_pages)
        for e in entries:
            self._map_entry(slot, int(e))
        return self.table[slot, np.asarray(entries, np.int64)].astype(
            np.int64
        ).copy()

    # -- device views ---------------------------------------------------------

    def device_rows(self, slots, active=None) -> jax.Array:
        """Device page table for ``slots`` with unmapped entries — and,
        when ``active`` is given, all entries of inactive slots — routed to
        the garbage page, so jitted reads stay in-bounds and jitted writes
        for slots the engine has not activated can never corrupt a live
        page."""
        rows = self.table[np.asarray(slots)]
        rows = np.where(rows < 0, self.garbage_page, rows)
        if active is not None:
            rows = np.where(
                np.asarray(active)[:, None], rows, self.garbage_page
            )
        return jnp.asarray(rows, jnp.int32)

    # -- defrag ---------------------------------------------------------------

    def defrag(self) -> np.ndarray:
        """Compact mapped pages to the lowest physical indices.

        Returns the storage-row permutation ``perm`` (length
        ``num_pages + 1``, garbage row fixed) such that the device update is
        ``new_leaf = leaf[:, perm]``; tables and the free list are updated
        in place."""
        perm = np.empty((self.num_pages + 1,), np.int64)
        nxt = 0
        for s in range(self.table.shape[0]):
            for e in range(self.pages_per_slot):
                old = self.table[s, e]
                if old >= 0:
                    perm[nxt] = old
                    self.table[s, e] = nxt
                    nxt += 1
        leftovers = sorted(
            set(range(self.num_pages)) - set(perm[:nxt].tolist())
        )
        perm[nxt : self.num_pages] = leftovers
        perm[self.num_pages] = self.num_pages  # garbage stays put
        self._free = list(range(self.num_pages - 1, nxt - 1, -1))
        return perm


KV_SCALE_DTYPE = jnp.float16  # per-token sidecar: f16 keeps the page <= 0.55x
KV_SCALE_FLOOR = 1e-8  # all-zero tokens: finite divide, q stays 0


def init_paged_blocks(cfg, n_blocks: int, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16, *, quantized: bool = False) -> Dict:
    """Paged KV storage for ``n_blocks`` stacked block repeats of an
    attention-only pattern: per position, ``k``/``v`` leaves shaped
    ``[n_blocks, num_pages + 1, page_size, KV*hd]`` (last row = garbage
    page).  A token's KV heads lie side by side in one row: ``KV*hd``
    lanes tile the TPU's (8|16, 128) layout where ``hd`` alone may not
    (switch-base: 12 x 64 = 768), so the device keeps the stack row-major,
    the layout the paged-attention kernel reads, and no call transposes it.

    With ``quantized=True`` the k/v leaves store int8 codes and each
    position additionally carries ``k_scale``/``v_scale`` sidecar leaves
    ``[n_blocks, num_pages + 1, page_size]`` (float16) — one scale per
    written token, shared across KV heads and head dim.  The sidecars ride
    the same pytree as the pools, so spill/restore, defrag, and tier
    re-splits move them with their pages for free.
    """
    assert pattern_is_pageable(cfg), "paged storage needs an attn-only pattern"
    row = cfg.num_kv_heads * cfg.head_dim
    if quantized:
        dtype = jnp.int8
    blocks: Dict[str, Dict] = {}
    for i, _spec in enumerate(cfg.layer_pattern):
        entry = {
            "k": jnp.zeros((n_blocks, num_pages + 1, page_size, row), dtype),
            "v": jnp.zeros((n_blocks, num_pages + 1, page_size, row), dtype),
        }
        if quantized:
            entry["k_scale"] = jnp.zeros(
                (n_blocks, num_pages + 1, page_size), KV_SCALE_DTYPE
            )
            entry["v_scale"] = jnp.zeros(
                (n_blocks, num_pages + 1, page_size), KV_SCALE_DTYPE
            )
        blocks[f"pos{i}"] = entry
    return blocks


def paged_block_bytes(blocks: Dict) -> int:
    """Bytes one physical page occupies across all of a tier's block leaves
    (the unit ``kv_bytes_*`` metrics are denominated in).  Scale sidecars
    count toward their page, so quantized pools meter honestly."""
    return sum(leaf.nbytes // leaf.shape[1] for leaf in jax.tree.leaves(blocks))


def dense_page_bytes(cfg, n_blocks: int, page_size: int, dtype=None) -> int:
    """Bytes one physical page would occupy at the *dense* activation dtype
    (``cfg.dtype`` unless overridden), across every pattern position — the
    exact dense counterpart of ``paged_block_bytes`` and the denominator of
    the ``kv_bytes_dense_equiv`` / ``attn_bytes_dense_step`` baselines,
    which must not shrink when the stored pool is quantized."""
    dtype = jnp.dtype(cfg.dtype) if dtype is None else jnp.dtype(dtype)
    return (
        2 * len(cfg.layer_pattern) * n_blocks * page_size
        * cfg.num_kv_heads * cfg.head_dim * dtype.itemsize
    )


# -- per-token KV quantization (pool storage codec) --------------------------


def quantize_kv_tokens(x: jax.Array):
    """``[..., KV, hd] -> (q int8 same shape, scale f16 [...])``: one scale
    per token, shared across KV heads and head dim (the sidecar layout).
    The scale is rounded to the f16 sidecar dtype *before* quantizing, so
    dequantization with the stored sidecar is the exact inverse."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-1, -2))
    scale = jnp.maximum(amax / 127.0, KV_SCALE_FLOOR).astype(KV_SCALE_DTYPE)
    s = scale.astype(jnp.float32)[..., None, None]
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv_pool(pool: jax.Array, scale: jax.Array,
                       dtype=jnp.bfloat16) -> jax.Array:
    """``pool [..., ps, KV*hd] int8 + scale [..., ps] -> dense-equivalent
    pool`` (test oracle; the serving consumers dequantize in VMEM)."""
    return (
        pool.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    ).astype(dtype)


# -- device-side paged reads/writes (pure; used inside jitted stage fns) -----


def paged_gather(pool: jax.Array, table: jax.Array, kv_heads: int) -> jax.Array:
    """One block's pool [P+1, ps, KV*hd], table [B, pps] -> dense ring view
    [B, pps*ps, KV, hd].  Garbage-routed entries gather junk that the ring
    position mask (``ring_key_positions`` validity) discards.

    Test oracle only: the serving hot paths attend straight off the pool
    through the table (``attention.paged_decode_attention`` /
    ``paged_chunk_attention`` — O(mapped pages) HBM traffic); this
    materialized O(B x max_len) copy exists so parity tests can rebuild the
    exact dense ring the fused path must reproduce."""
    B, pps = table.shape
    buf = pool[table]  # [B, pps, ps, KV*hd]
    return buf.reshape(B, pps * pool.shape[1], kv_heads, -1)


def _rows(table: jax.Array, positions: jax.Array, page_size: int):
    """(physical page, offset) of ring positions ``positions`` [B, ...]
    through the slots' page tables [B, pps]."""
    B, pps = table.shape
    entry = jnp.mod(positions // page_size, pps).reshape(B, -1)
    phys = jnp.take_along_axis(table, entry, axis=1).reshape(positions.shape)
    return phys, jnp.mod(positions, page_size)


def paged_ring_write(pool_k: jax.Array, pool_v: jax.Array, k, v,
                     table: jax.Array, lengths: jax.Array, page_size: int,
                     layer):
    """Write one new token's k/v ([B, 1, KV, hd]) at ring position
    ``lengths`` through the page table into block ``layer`` of the stacked
    pools (paged analogue of :func:`ring_write`): a scatter of B rows,
    which XLA does in place."""
    phys, off = _rows(table, lengths, page_size)
    B = k.shape[0]
    pool_k = pool_k.at[layer, phys, off].set(
        k[:, 0].reshape(B, -1).astype(pool_k.dtype))
    pool_v = pool_v.at[layer, phys, off].set(
        v[:, 0].reshape(B, -1).astype(pool_v.dtype))
    return pool_k, pool_v


def paged_ring_write_quant(pool_k, pool_v, pool_ks, pool_vs, k, v,
                           table: jax.Array, lengths: jax.Array,
                           page_size: int, layer):
    """Quantize-on-write variant of :func:`paged_ring_write`: the token's
    k/v are int8-quantized with one per-token scale each (shared across KV
    heads and head dim) and both the codes and the f16 scale sidecars are
    scattered through the page table into block ``layer``."""
    phys, off = _rows(table, lengths, page_size)
    B = k.shape[0]
    qk, sk = quantize_kv_tokens(k[:, 0])
    qv, sv = quantize_kv_tokens(v[:, 0])
    pool_k = pool_k.at[layer, phys, off].set(qk.reshape(B, -1))
    pool_v = pool_v.at[layer, phys, off].set(qv.reshape(B, -1))
    pool_ks = pool_ks.at[layer, phys, off].set(sk)
    pool_vs = pool_vs.at[layer, phys, off].set(sv)
    return pool_k, pool_v, pool_ks, pool_vs


def paged_write_tokens(pool_k: jax.Array, pool_v: jax.Array, k, v,
                       table: jax.Array, positions: jax.Array,
                       valid: jax.Array, page_size: int, layer):
    """Write a chunk of tokens ([B, C, KV, hd]) at ``positions`` [B, C]
    through the page table into block ``layer`` of the stacked pools; rows
    where ``valid`` [B, C] is False (prompt padding) are routed to the
    garbage page."""
    phys, off = _rows(table, positions, page_size)
    phys = jnp.where(valid, phys, pool_k.shape[1] - 1)
    B, C = positions.shape
    pool_k = pool_k.at[layer, phys, off].set(
        k.reshape(B, C, -1).astype(pool_k.dtype))
    pool_v = pool_v.at[layer, phys, off].set(
        v.reshape(B, C, -1).astype(pool_v.dtype))
    return pool_k, pool_v


def paged_write_tokens_quant(pool_k, pool_v, pool_ks, pool_vs, k, v,
                             table: jax.Array, positions: jax.Array,
                             valid: jax.Array, page_size: int, layer):
    """Quantize-on-write variant of :func:`paged_write_tokens` (chunked
    prefill): per-token int8 codes plus f16 scale sidecars, padding rows
    routed to the garbage page exactly like the dense-dtype path."""
    phys, off = _rows(table, positions, page_size)
    phys = jnp.where(valid, phys, pool_k.shape[1] - 1)
    B, C = positions.shape
    qk, sk = quantize_kv_tokens(k)
    qv, sv = quantize_kv_tokens(v)
    pool_k = pool_k.at[layer, phys, off].set(qk.reshape(B, C, -1))
    pool_v = pool_v.at[layer, phys, off].set(qv.reshape(B, C, -1))
    pool_ks = pool_ks.at[layer, phys, off].set(sk)
    pool_vs = pool_vs.at[layer, phys, off].set(sv)
    return pool_k, pool_v, pool_ks, pool_vs


# -- tier re-splits over pages ----------------------------------------------


def page_perm(src_tables: np.ndarray, dst_tables: np.ndarray,
              src_pages: int, dst_pages: int) -> np.ndarray:
    """Physical-row permutation carrying one engine's pages from a source
    pool's index space to a destination pool's (used when a replan moves
    blocks between tiers: the two pools map the same (slot, entry) set —
    allocation is lockstep — but may assign different physical indices;
    with a fleet-shared cloud pool the slot rows are the lane's own block).

    ``src_tables``/``dst_tables`` are aligned ``[n_slots, pps]`` table
    slices.  Returns ``perm`` with ``len == dst_pages + 1`` such that
    ``dst_leaf = src_leaf[:, perm]`` places every mapped page at its
    destination row; unmapped destination rows read arbitrary (dead) data.
    """
    perm = np.zeros((dst_pages + 1,), np.int64)
    perm[dst_pages] = src_pages  # garbage -> garbage
    for src_row, dst_row in zip(np.asarray(src_tables), np.asarray(dst_tables)):
        if not np.array_equal(src_row >= 0, dst_row >= 0):
            raise ValueError(
                f"tier pools out of lockstep "
                f"({src_row.tolist()} vs {dst_row.tolist()})"
            )
        for e in range(len(src_row)):
            if dst_row[e] >= 0:
                perm[dst_row[e]] = src_row[e]
    return perm


def resplit_paged_blocks(end_blocks: Dict, cloud_blocks: Dict,
                         old_split: int, new_split: int,
                         end_to_cloud: np.ndarray,
                         cloud_to_end: np.ndarray) -> Tuple[Dict, Dict]:
    """Move block repeats between the tiers' paged storages at a replan
    safe point (the paged analogue of ``merge_cache`` + ``split_cache``):
    the moved leaves' page rows are permuted from the source pool's index
    space into the destination pool's."""
    if new_split == old_split:
        return end_blocks, cloud_blocks

    if new_split < old_split:  # end -> cloud
        def move(e_leaf, c_leaf):
            moved = e_leaf[new_split:][:, jnp.asarray(end_to_cloud)]
            return e_leaf[:new_split], jnp.concatenate([moved, c_leaf], axis=0)
    else:  # cloud -> end
        def move(e_leaf, c_leaf):
            n = new_split - old_split
            moved = c_leaf[:n][:, jnp.asarray(cloud_to_end)]
            return jnp.concatenate([e_leaf, moved], axis=0), c_leaf[n:]

    pairs = jax.tree.map(move, end_blocks, cloud_blocks)
    end_new = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    cloud_new = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return end_new, cloud_new
