"""Causal GQA flash-attention forward Pallas kernel (prefill hot path).

Grid: (batch, q-head, q-blocks, kv-blocks) with kv minor-most.  The online
softmax state (m, l) and the output accumulator live in VMEM scratch and
persist across the kv sweep for a fixed (b, h, i); the output block is
written once when the sweep finishes.  Causal masking is block-aware: fully
masked kv blocks (j > i) are skipped with pl.when so they cost neither MXU
flops nor VMEM traffic — this is the "causal block skipping" the pure-XLA
scan path cannot express (see EXPERIMENTS.md §Perf).

GQA is handled in the index maps: kv blocks are fetched from head h // G,
so no repeated-KV materialization happens in HBM.

VMEM per step (bq=bk=512, hd=128, bf16 in / fp32 acc):
  q 512x128x2 = 128 KiB, k/v 2x512x128x2 = 256 KiB,
  s 512x512x4 = 1 MiB, acc 512x128x4 = 256 KiB — comfortably resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref,  # [1, 1, bq, hd]
    k_ref,  # [1, 1, bk, hd]
    v_ref,  # [1, 1, bk, hd]
    o_ref,  # [1, 1, bq, hd]
    m_scr,  # [bq, 1] fp32
    l_scr,  # [bq, 1] fp32
    acc_scr,  # [bq, hd] fp32
    *,
    scale: float,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    window,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level causal/window skip: only touch kv blocks that intersect
    # the visible band for this q block.
    live = jnp.bool_(True)
    if causal:
        live = j <= i
    if window is not None:
        # lowest visible key for this q block = i*bq - window + 1
        live = jnp.logical_and(live, (j + 1) * bk - 1 >= i * bq - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.bool_(True)
        if causal:
            mask = qpos >= kpos
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q,  # [B, H, Sq, hd]
    k,  # [B, KV, Skv, hd]
    v,
    *,
    causal=True,
    window=None,
    block_q=512,
    block_kv=512,
    interpret=False,
):
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _fa_kernel, scale=scale, bq=bq, bk=bk, nk=nk, causal=causal, window=window
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
