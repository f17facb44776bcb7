"""Fused paged decode/chunk-attention Pallas kernel (decode hot path).

Grid: (batch, page-entry) with the page sweep minor-most.  The per-slot
page table is a *scalar-prefetch* operand, so the K/V BlockSpec index maps
resolve ``table[b, j]`` before each grid step and DMA exactly one physical
page of the pool — ``[page_size, KV, hd]``, every KV head at once — from
HBM to VMEM.  No dense ``[B, pps*ps, KV, hd]`` ring view is ever
materialized: HBM traffic per slot is its mapped pages, not ``max_len``.

TPU tiling: every block's last two dims are whole array dims (``(KV, hd)``
for the pages, ``(rows, hd)`` for the queries, ``(1, ps)`` for the scale
sidecars), so the kernel compiles for any KV-head count.  A block of one KV
head, ``(1, ps, 1, hd)``, would put a size-1 block in the second-minor
position, which the TPU lowering refuses unless KV == 1.

The online-softmax state (m, l) and the output accumulator live in VMEM
scratch, one slice per KV head, and persist across the page sweep for a
fixed slot; a static loop over the KV heads folds each fetched page into
every head's state, and the output block is written once when the sweep
flushes.

Page-skip rule: a page is *dead* when its table entry is garbage-routed
(unmapped entry or inactive slot — the engines map those to the pool's
last row) or when every (query, ring-position) pair it holds is masked
(positions not yet written on this lap, or wholly outside the sliding
window).  Dead pages are skipped with ``pl.when``: no MXU flops, no
softmax update.  All garbage entries map to the *same* physical row, so
Pallas's block-index pipelining elides their repeated fetches; a mapped
but window-dead page still costs its (single) fetch but no compute.

GQA is handled in the row layout: the wrapper flattens (C queries x G
query heads per KV head) into ``rows = C*G`` q rows per KV head,
``row = c*G + g``, so all of a KV head's queries stream against the page.

Masking matches ``kvcache.ring_key_positions`` + ``chunk_attention``: ring
slot ``s = j*ps + i`` holds position ``kp = ln - ((ln - s) mod W)`` where
``ln`` is the slot's last written position and ``W = pps*ps``; a key is
visible iff ``0 <= kp <= qpos`` (and ``kp > qpos - window``).  Per-slot
``ln`` and per-query ``qpos`` arrive as one int32 operand
``posinfo[B, 1+C, 1]`` (column 0 = ln, rest = qpos) so the trace depends
only on shapes.

Quantized pools: with ``pool_ks``/``pool_vs`` sidecars the k/v pools hold
int8 codes and the per-token scale row of each fetched page rides the same
``tab[b, j]`` scalar-prefetched indirection as the page itself.  The pool
stores the scales as f16 (``kvcache.KV_SCALE_DTYPE``); Mosaic on v5e does
not load f16 vectors, so the wrapper widens the ``[P+1, ps]`` sidecar to
float32 first (an elementwise pass over ``1/(KV*hd)`` of the int8 pool's
bytes, exact: every f16 value is an f32 value).
The scales are folded in VMEM where they broadcast along lanes: into the
scores (``s = (q . k_codes) * k_scale``) and into the probabilities
(``(p * v_scale) @ v_codes``), which equals attention over the dequantized
page.  HBM traffic per page is the int8 bytes plus two ``[ps]`` scale rows,
and no dequantized copy ever exists outside VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_body(
    table_ref,  # [B, pps] int32 (scalar prefetch, SMEM)
    q_ref,  # [1, KV, rows, hd]
    k_ref,  # [1, ps, KV, hd] one physical page, all kv heads
    v_ref,  # [1, ps, KV, hd]
    ks_ref,  # [1, 1, ps] f32 per-token scale sidecar | None (dense pool)
    vs_ref,  # [1, 1, ps] | None
    pos_ref,  # [1, 1+C, 1] int32 (ln, then C query positions)
    o_ref,  # [1, KV, rows, hd]
    m_scr,  # [KV, rows, 1] fp32
    l_scr,  # [KV, rows, 1] fp32
    acc_scr,  # [KV, rows, hd] fp32
    *,
    scale: float,
    ps: int,
    pps: int,
    C: int,
    G: int,
    KV: int,
    window,
    garbage: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    phys = table_ref[b, j]
    ln = pos_ref[0, 0, 0]
    qpos = pos_ref[0, 1:, :]  # [C, 1]
    W = pps * ps
    slot = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    kp = ln - jnp.mod(ln - slot, W)  # [1, ps] ring position per key row
    valid = kp <= qpos  # [C, ps]
    if window is not None:
        valid = jnp.logical_and(valid, kp > qpos - window)
    valid = jnp.logical_and(valid, kp >= 0)
    live = jnp.logical_and(phys != garbage, jnp.any(valid))

    @pl.when(live)
    def _compute():
        mask = jnp.broadcast_to(valid[:, None, :], (C, G, ps)).reshape(C * G, ps)
        quantized = ks_ref is not None
        if quantized:
            k_sc = ks_ref[0]  # [1, ps] broadcasts over the score rows
            v_sc = vs_ref[0]
        for h in range(KV):  # static: one fetched page serves every head
            q = q_ref[0, h]  # [rows, hd]
            k = k_ref[0, :, h, :]  # [ps, hd]
            v = v_ref[0, :, h, :]
            if quantized:
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [rows, ps]
            if quantized:
                s = s * k_sc
            s = jnp.where(mask, s * scale, NEG_INF)

            m_prev = m_scr[h]  # [rows, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            pv = p * v_sc if quantized else p
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new

    @pl.when(j == pps - 1)
    def _flush():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _pa_kernel(table_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
               m_scr, l_scr, acc_scr, **kw):
    _pa_body(table_ref, q_ref, k_ref, v_ref, None, None, pos_ref, o_ref,
             m_scr, l_scr, acc_scr, **kw)


def _pa_kernel_quant(table_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     pos_ref, o_ref, m_scr, l_scr, acc_scr, **kw):
    _pa_body(table_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, pos_ref, o_ref,
             m_scr, l_scr, acc_scr, **kw)


def paged_attention_pallas(
    q_r,  # [B, KV, rows, hd] with rows = C*G, row = c*G + g
    pool_k,  # [P+1, ps, KV, hd] (row P = garbage page)
    pool_v,
    table,  # [B, pps] int32
    posinfo,  # [B, 1+C, 1] int32
    *,
    pool_ks=None,  # [P+1, ps] per-token scale sidecar (quantized pool)
    pool_vs=None,
    window=None,
    interpret=False,
):
    B, KV, rows, hd = q_r.shape
    ps = pool_k.shape[1]
    pps = table.shape[1]
    C = posinfo.shape[1] - 1
    G = rows // C
    garbage = pool_k.shape[0] - 1
    scale = 1.0 / (hd ** 0.5)
    quantized = pool_ks is not None

    kernel = functools.partial(
        _pa_kernel_quant if quantized else _pa_kernel,
        scale=scale, ps=ps, pps=pps, C=C, G=G, KV=KV,
        window=window, garbage=garbage,
    )
    page = pl.BlockSpec((1, ps, KV, hd), lambda b, j, tab: (tab[b, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, KV, rows, hd), lambda b, j, tab: (b, 0, 0, 0)),
        page,
        page,
    ]
    args = [q_r, pool_k, pool_v]
    if quantized:
        # the sidecars ride the same scalar-prefetched page indirection as
        # the pools: one [1, ps] float32 row per fetched page (a [P+1, 1, ps]
        # view, so the block's last two dims are whole array dims; widened
        # from the stored f16, which Mosaic does not load)
        side = pl.BlockSpec((1, 1, ps), lambda b, j, tab: (tab[b, j], 0, 0))
        in_specs += [side, side]
        args += [
            s.astype(jnp.float32).reshape(s.shape[0], 1, ps)
            for s in (pool_ks, pool_vs)
        ]
    in_specs.append(pl.BlockSpec((1, C + 1, 1), lambda b, j, tab: (b, 0, 0)))
    args.append(posinfo)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, rows, hd), lambda b, j, tab: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, rows, 1), jnp.float32),
            pltpu.VMEM((KV, rows, 1), jnp.float32),
            pltpu.VMEM((KV, rows, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_attention",  # stable kernel name in HLO and traces
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q_r.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), *args)
