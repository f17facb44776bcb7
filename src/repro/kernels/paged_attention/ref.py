"""Oracle: gather-free paged decode/chunk attention in pure JAX.

The parity target for the Pallas kernel in ``kernel.py`` — and the
implementation the models layer dispatches to off-TPU.  It takes the same
operands: a tier's pools stacked over its blocks, ``[R, P+1, ps, KV*hd]``,
and the block to attend.  Instead of materializing a dense
``[B, pps*ps, KV, hd]`` ring view of the page pool
(``kvcache.paged_gather``) and sweeping all of it, the softmax loop scans
the page *table*: each step indexes ``pool[layer, table[:, e]]`` — one
physical page per slot — masks the page's ring positions against the
queries, and folds it into an online-softmax accumulator.  KV traffic per step is one
page per (slot, entry) instead of the whole ring, and nothing is ever
written back to HBM between the pool and the output.

Page-skip rule (shared with the kernel, so the two are numerically
identical even on rows whose output is garbage-and-discarded): a (slot,
entry) page contributes nothing when its table entry is garbage-routed
(unmapped entry / inactive slot) or when every (query, position) pair in
it is masked.  Live rows always keep their exact softmax — a skipped
page's keys would have carried zero probability anyway — and rows with no
valid key at all come back 0 instead of the dense path's
uniform-over-garbage junk (both are discarded by the engines).

Masking matches ``models.attention.chunk_attention`` bit for bit: ring
entry ``e`` holds positions ``lengths - ((lengths - (e*ps + i)) mod W)``
(``kvcache.ring_key_positions``), a key is visible iff ``0 <= kp <= qpos``
and, with a sliding window, ``kp > qpos - window``.

Quantized pools (``k_scale``/``v_scale`` given): the k/v leaves hold int8
codes and each gathered page is dequantized in registers — one f16 scale
per token row (``kvcache.quantize_kv_tokens``) — before the score and
value einsums, mirroring exactly the fused in-VMEM dequant of the kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(
    q: jax.Array,  # [B, C, H, hd] (C=1 for decode)
    pool_k: jax.Array,  # [R, P+1, ps, KV*hd] (row P = garbage page)
    pool_v: jax.Array,
    layer: jax.Array,  # int32 scalar: the block of the stack to attend
    table: jax.Array,  # [B, pps] int32 physical page per ring entry
    q_positions: jax.Array,  # [B, C] int32 absolute position of each query
    lengths: jax.Array,  # [B] int32 ring anchor (position of the last write)
    *,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [R, P+1, ps] f16 per-token sidecar
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    B, C, H, hd = q.shape
    ps, KV = pool_k.shape[2], pool_k.shape[3] // hd
    pps = table.shape[1]
    W = pps * ps
    G = H // KV
    garbage = pool_k.shape[1] - 1
    scale = 1.0 / (hd ** 0.5)
    qr = q.reshape(B, C, KV, G, hd)
    ln = lengths[:, None].astype(jnp.int32)  # [B, 1]
    qpos = q_positions.astype(jnp.int32)

    def page_step(carry, e):
        m, l, acc = carry
        phys = table[:, e]  # [B]
        k_page = pool_k[layer, phys].reshape(B, ps, KV, hd)
        v_page = pool_v[layer, phys].reshape(B, ps, KV, hd)
        if k_scale is not None:
            # quantized pool: dequantize the gathered page in registers —
            # one f16 scale per token row, shared across heads and head dim
            k_page = k_page.astype(jnp.float32) * (
                k_scale[layer, phys].astype(jnp.float32)[:, :, None, None]
            )
            v_page = v_page.astype(jnp.float32) * (
                v_scale[layer, phys].astype(jnp.float32)[:, :, None, None]
            )
        slot = e * ps + jnp.arange(ps, dtype=jnp.int32)[None, :]  # [1, ps]
        kp = ln - jnp.mod(ln - slot, W)  # [B, ps]
        valid = kp[:, None, :] <= qpos[:, :, None]  # [B, C, ps]
        if window is not None:
            valid &= kp[:, None, :] > qpos[:, :, None] - window
        valid &= kp[:, None, :] >= 0
        live = (phys != garbage) & valid.any(axis=(1, 2))  # [B] page skip
        s = jnp.einsum(
            "bcgnd,bkgd->bcgnk", qr, k_page, preferred_element_type=jnp.float32
        ) * scale  # [B, C, KV, G, ps]
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum(
            "bcgnk,bkgd->bcgnd", p.astype(v_page.dtype), v_page,
            preferred_element_type=jnp.float32,
        )
        keep = live[:, None, None, None]
        m = jnp.where(keep, m_new, m)
        l = jnp.where(keep, l * corr + p.sum(axis=-1), l)
        acc = jnp.where(keep[..., None], acc * corr[..., None] + pv, acc)
        return (m, l, acc), None

    init = (
        jnp.full((B, C, KV, G), NEG_INF, jnp.float32),
        jnp.zeros((B, C, KV, G), jnp.float32),
        jnp.zeros((B, C, KV, G, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        page_step, init, jnp.arange(pps, dtype=jnp.int32)
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]
    return out.reshape(B, C, H, hd).astype(q.dtype)
