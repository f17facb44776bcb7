"""Attention: GQA projections, RoPE / M-RoPE, flash (blocked, online-softmax)
self-attention with a custom-VJP FlashAttention-2 style backward pass, and
single-token decode attention over (possibly ring-buffered) KV caches.

The flash implementation is pure JAX (scans over q/kv blocks) so it lowers
on any backend; it is also the numerical oracle for the Pallas flash kernel
in ``repro.kernels.flash_attention``.  Memory is O(S · block) instead of
O(S^2), which is what makes the 32k-prefill / 4k-train cells fit HBM in the
dry-run.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import rms_norm, truncated_normal_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_angles(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    mrope_sections: Optional[Tuple[int, ...]] = None,
) -> jax.Array:
    """positions: [B, S] (standard) or [B, 3, S] (M-RoPE).
    Returns angles [B, S, head_dim // 2] (fp32)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[:, 0]
        return positions[..., None].astype(jnp.float32) * freqs
    assert positions.ndim == 3, "M-RoPE needs [B, 3, S] positions"
    assert sum(mrope_sections) == half, (mrope_sections, half)
    parts = []
    start = 0
    for comp, sec in enumerate(mrope_sections):
        p = positions[:, comp].astype(jnp.float32)  # [B, S]
        parts.append(p[..., None] * freqs[start : start + sec])
        start += sec
    return jnp.concatenate(parts, axis=-1)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: [B, S, n, head_dim]; angles: [B, S, head_dim//2]."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1).astype(
        dtype
    )


# ---------------------------------------------------------------------------
# Flash attention (blocked, custom VJP)
# ---------------------------------------------------------------------------


def _block_mask(qpos, kpos, causal: bool, window: Optional[int]):
    """qpos: [qc], kpos: [kc] -> bool [qc, kc] (True = attend)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def _flash_fwd_inner(q_blk, k_r, v_r, qpos, kpos_all, causal, window, scale, kc):
    """Online-softmax over kv blocks for one q block.

    q_blk: [B, qc, KV, G, hd]; k_r/v_r: [nk, B, kc, KV, hd].
    Returns (out [B, qc, KV, G, hd] fp32, lse [B, qc, KV, G] fp32)."""
    B, qc, KV, G, hd = q_blk.shape
    nk = k_r.shape[0]

    def step(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, j = inp
        s = jnp.einsum(
            "bqgnd,bkgd->bqgnk", q_blk, k_blk, preferred_element_type=jnp.float32
        ) * scale  # [B, qc, KV, G, kc]
        kpos = j * kc + jnp.arange(kc)
        mask = _block_mask(qpos, kpos, causal, window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bqgnk,bkgd->bqgnd",
            p.astype(v_blk.dtype),
            v_blk,
            preferred_element_type=jnp.float32,
        )
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    init = (
        jnp.full((B, qc, KV, G), NEG_INF, jnp.float32),
        jnp.zeros((B, qc, KV, G), jnp.float32),
        jnp.zeros((B, qc, KV, G, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(step, init, (k_r, v_r, jnp.arange(nk)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return out, lse


def _reshape_qkv(q, k, v):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    return q, k, v, (B, Sq, H, hd, KV, G)


@functools.lru_cache(maxsize=None)
def make_flash_attention(
    causal: bool,
    window: Optional[int],
    q_chunk: int,
    kv_chunk: int,
    scale: float,
    q_offset: int = 0,
):
    """Build a custom-VJP flash attention fn for static settings.

    Returned fn: (q [B,Sq,H,hd], k [B,Skv,KV,hd], v, qpos [Sq] int32)
    -> out [B,Sq,H,hd].  ``qpos`` carries the (possibly dynamic, e.g.
    sequence-parallel shard-offset) absolute position of every query row;
    key positions are 0..Skv-1.
    """

    @jax.custom_vjp
    def fa(q, k, v, qpos):
        return _fwd(q, k, v, qpos)[0]

    def _fwd(q, k, v, qpos):
        in_dtype = q.dtype
        qr, k_, v_, (B, Sq, H, hd, KV, G) = _reshape_qkv(q, k, v)
        Skv = k.shape[1]
        qc = min(q_chunk, Sq)
        kc = min(kv_chunk, Skv)
        assert Sq % qc == 0 and Skv % kc == 0, (Sq, qc, Skv, kc)
        nq, nk = Sq // qc, Skv // kc
        q_r = qr.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        k_r = k_.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
        v_r = v_.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
        qpos_r = qpos.reshape(nq, qc)

        def per_q_block(qp, q_blk):
            return _flash_fwd_inner(
                q_blk, k_r, v_r, qp, None, causal, window, scale, kc
            )

        out_r, lse_r = jax.lax.map(
            lambda args: per_q_block(*args), (qpos_r, q_r)
        )
        out = (
            out_r.transpose(1, 0, 2, 3, 4, 5)
            .reshape(B, Sq, H, hd)
            .astype(in_dtype)
        )
        lse = lse_r.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H)
        return out, (q, k, v, out, lse, qpos)

    def _bwd(res, do):
        q, k, v, out, lse, qpos = res
        in_dtype = q.dtype
        qr, k_, v_, (B, Sq, H, hd, KV, G) = _reshape_qkv(q, k, v)
        Skv = k.shape[1]
        qc = min(q_chunk, Sq)
        kc = min(kv_chunk, Skv)
        nq, nk = Sq // qc, Skv // kc
        do_f = do.astype(jnp.float32)
        # D_i = rowsum(dO * O)
        delta = jnp.sum(do_f * out.astype(jnp.float32), axis=-1)  # [B, Sq, H]

        q_r = qr.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        k_r = k_.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
        v_r = v_.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
        do_r = (
            do_f.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        )
        lse_r = lse.reshape(B, nq, qc, KV, G).transpose(1, 0, 2, 3, 4)
        dl_r = delta.reshape(B, nq, qc, KV, G).transpose(1, 0, 2, 3, 4)
        qpos_r = qpos.reshape(nq, qc)

        def scores(q_blk, k_blk, qp, j):
            s = jnp.einsum(
                "bqgnd,bkgd->bqgnk", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale
            kpos = j * kc + jnp.arange(kc)
            mask = _block_mask(qp, kpos, causal, window)
            return jnp.where(mask[None, :, None, None, :], s, NEG_INF)

        # Pass 1: dq (scan over q blocks; inner scan over kv blocks)
        def dq_block(args):
            qp, q_blk, do_blk, lse_blk, dl_blk = args

            def step(dq_acc, inp):
                k_blk, v_blk, j = inp
                s = scores(q_blk, k_blk, qp, j)
                p = jnp.exp(s - lse_blk[..., None])
                dp = jnp.einsum(
                    "bqgnd,bkgd->bqgnk", do_blk, v_blk,
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - dl_blk[..., None]) * scale
                dq_acc = dq_acc + jnp.einsum(
                    "bqgnk,bkgd->bqgnd", ds.astype(k_blk.dtype), k_blk,
                    preferred_element_type=jnp.float32,
                )
                return dq_acc, None

            init = jnp.zeros_like(q_blk, jnp.float32)
            dq_blk, _ = jax.lax.scan(step, init, (k_r, v_r, jnp.arange(nk)))
            return dq_blk

        dq_r = jax.lax.map(dq_block, (qpos_r, q_r, do_r, lse_r, dl_r))

        # Pass 2: dk, dv (scan over kv blocks; inner scan over q blocks)
        def dkv_block(args):
            j, k_blk, v_blk = args

            def step(carry, inp):
                dk_acc, dv_acc = carry
                qp, q_blk, do_blk, lse_blk, dl_blk = inp
                s = scores(q_blk, k_blk, qp, j)
                p = jnp.exp(s - lse_blk[..., None])
                dv_acc = dv_acc + jnp.einsum(
                    "bqgnk,bqgnd->bkgd", p.astype(do_blk.dtype), do_blk,
                    preferred_element_type=jnp.float32,
                )
                dp = jnp.einsum(
                    "bqgnd,bkgd->bqgnk", do_blk, v_blk,
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - dl_blk[..., None]) * scale
                dk_acc = dk_acc + jnp.einsum(
                    "bqgnk,bqgnd->bkgd", ds, q_blk.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
                return (dk_acc, dv_acc), None

            init = (
                jnp.zeros(k_blk.shape, jnp.float32),
                jnp.zeros(v_blk.shape, jnp.float32),
            )
            (dk_blk, dv_blk), _ = jax.lax.scan(
                step, init, (qpos_r, q_r, do_r, lse_r, dl_r)
            )
            return dk_blk, dv_blk

        dk_r, dv_r = jax.lax.map(dkv_block, (jnp.arange(nk), k_r, v_r))

        dq = (
            dq_r.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd).astype(in_dtype)
        )
        dk = dk_r.transpose(1, 0, 2, 3, 4).reshape(B, Skv, KV, hd).astype(in_dtype)
        dv = dv_r.transpose(1, 0, 2, 3, 4).reshape(B, Skv, KV, hd).astype(in_dtype)
        import numpy as _np

        dqpos = _np.zeros(qpos.shape, jax.dtypes.float0)
        return dq, dk, dv, dqpos

    fa.defvjp(_fwd, _bwd)
    return fa


def _fit_chunk(S: int, c: int) -> int:
    """Largest divisor of S that is <= c."""
    c = min(c, S)
    while S % c:
        c -= 1
    return c


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    q_offset: int = 0,
    q_positions: Optional[jax.Array] = None,  # [Sq] (overrides q_offset)
) -> jax.Array:
    scale = 1.0 / (q.shape[-1] ** 0.5)
    q_chunk = _fit_chunk(q.shape[1], q_chunk)
    kv_chunk = _fit_chunk(k.shape[1], kv_chunk)
    fn = make_flash_attention(causal, window, q_chunk, kv_chunk, scale)
    if q_positions is None:
        q_positions = q_offset + jnp.arange(q.shape[1], dtype=jnp.int32)
    return fn(q, k, v, q_positions)


def reference_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """O(S^2)-memory oracle used by tests."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qr = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqgnd,bkgd->bqgnk", qr.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(k.shape[1])
    mask = _block_mask(qpos, kpos, causal, window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqgnk,bkgd->bqgnd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single new token vs. cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k_cache: jax.Array,  # [B, S, KV, hd]
    v_cache: jax.Array,
    q_positions: jax.Array,  # [B] int32 current position of the query token
    key_positions: jax.Array,  # [B, S] int32 position held by each cache slot
    window: Optional[int] = None,
) -> jax.Array:
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum(
        "bgnd,bkgd->bgnk", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale  # [B, KV, G, S]
    valid = key_positions <= q_positions[:, None]
    if window is not None:
        valid &= key_positions > (q_positions[:, None] - window)
    valid &= key_positions >= 0
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bgnk,bkgd->bgnd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def chunk_attention(
    q: jax.Array,  # [B, C, H, hd] one prefill chunk of queries
    k_cache: jax.Array,  # [B, S, KV, hd] ring view (pages gathered)
    v_cache: jax.Array,
    q_positions: jax.Array,  # [B, C] int32 absolute position of each query
    key_positions: jax.Array,  # [B, S] int32 position held by each cache slot
    window: Optional[int] = None,
) -> jax.Array:
    """Chunked-prefill attention: C queries against a (possibly
    ring-buffered / paged) KV cache that already contains the chunk's own
    k/v.  Per-query causal masking over absolute positions — the C=1 case
    is exactly :func:`decode_attention`.  O(C·S) memory, no materialized
    [S, S] score matrix, which is what lets admission stream a long prompt
    through fixed-shape chunk traces."""
    B, C, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qr = q.reshape(B, C, KV, G, hd)
    s = jnp.einsum(
        "bcgnd,bkgd->bcgnk", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale  # [B, C, KV, G, S]
    valid = key_positions[:, None, :] <= q_positions[:, :, None]  # [B, C, S]
    if window is not None:
        valid &= key_positions[:, None, :] > (q_positions[:, :, None] - window)
    valid &= key_positions[:, None, :] >= 0
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bcgnk,bkgd->bcgnd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, C, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged attention (gather-free decode/chunk attention over a KV page pool)
# ---------------------------------------------------------------------------

_PAGED_ATTN_IMPL: Optional[str] = None  # None = auto: kernel on TPU, ref off


def set_paged_attention_impl(impl: Optional[str]):
    """Force the paged-attention implementation: ``"kernel"`` (the fused
    Pallas kernel — interpret-mode off TPU), ``"ref"`` (the pure-JAX
    gather-free oracle), or ``None`` to autodetect (kernel on TPU, ref
    elsewhere).  Read at *trace* time: engines built before a change keep
    their already-compiled stage traces."""
    global _PAGED_ATTN_IMPL
    if impl not in (None, "kernel", "ref"):
        raise ValueError(f"impl={impl!r} (want 'kernel', 'ref', or None)")
    _PAGED_ATTN_IMPL = impl


def paged_chunk_attention(
    q: jax.Array,  # [B, C, H, hd] C queries (decode is the C=1 case)
    pool_k: jax.Array,  # [R, P+1, ps, KV*hd] pools of R blocks (row P = garbage)
    pool_v: jax.Array,
    layer: jax.Array,  # int32 scalar: the block of the stack to attend
    table: jax.Array,  # [B, pps] int32 physical page per ring entry
    q_positions: jax.Array,  # [B, C] int32 absolute position of each query
    lengths: jax.Array,  # [B] int32 ring anchor (last written position)
    *,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [R, P+1, ps] f16 sidecar (int8 pool)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention straight off the page pool: page-table lookup, ring-position
    masking (``kvcache.ring_key_positions`` semantics), and online-softmax
    attention fused into one sweep over the slot's *mapped* pages of block
    ``layer`` — no dense ``paged_gather`` ring view is ever materialized, and
    the stack is read where it lies, never sliced per layer.  Numerically the masked
    softmax of :func:`chunk_attention` over the gathered ring (exact in the
    score set; online-softmax reassociation only), which survives as the
    test oracle.  With ``k_scale``/``v_scale`` the pools hold int8 codes and
    both implementations dequantize the fetched pages in place (VMEM /
    registers) — no dense-dtype copy of the pool is ever materialized."""
    impl = _PAGED_ATTN_IMPL or (
        "kernel" if jax.default_backend() == "tpu" else "ref"
    )
    if impl == "kernel":
        from repro.kernels.paged_attention.ops import paged_attention

        return paged_attention(
            q, pool_k, pool_v, layer, table, q_positions, lengths,
            window=window, k_scale=k_scale, v_scale=v_scale,
        )
    from repro.kernels.paged_attention.ref import paged_attention_ref

    return paged_attention_ref(
        q, pool_k, pool_v, layer, table, q_positions, lengths,
        window=window, k_scale=k_scale, v_scale=v_scale,
    )


def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    pool_k: jax.Array,
    pool_v: jax.Array,
    layer: jax.Array,
    table: jax.Array,
    lengths: jax.Array,  # [B] int32 position of the current (just-written) token
    *,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token decode against the page pool: the C=1 special case of
    :func:`paged_chunk_attention` (the query sits at ``lengths``, which is
    also the ring anchor)."""
    return paged_chunk_attention(
        q, pool_k, pool_v, layer, table, lengths[:, None], lengths,
        window=window, k_scale=k_scale, v_scale=v_scale,
    )


# ---------------------------------------------------------------------------
# Attention layer (projections + norm + rope + attention + output)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype) -> Dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal_init(kq, (d, H * hd), dtype, 1.0).reshape(d, H, hd),
        "wk": truncated_normal_init(kk, (d, KV * hd), dtype, 1.0).reshape(d, KV, hd),
        "wv": truncated_normal_init(kv, (d, KV * hd), dtype, 1.0).reshape(d, KV, hd),
        "wo": truncated_normal_init(ko, (H * hd, d), dtype, 1.0).reshape(H, hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def project_qkv(params: Dict, x: jax.Array, cfg, angles: Optional[jax.Array]):
    """x: [B, S, d] -> q [B,S,H,hd], k,v [B,S,KV,hd] (rope+qknorm applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    return q, k, v


def output_proj(params: Dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype))
