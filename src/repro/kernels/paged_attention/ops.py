"""Public wrapper for the fused paged-attention kernel.

Layout contract: the models keep ``[B, C, H, hd]`` queries and a tier's
page pools stacked over its blocks, ``[R, P+1, page_size, KV*hd]``, with
the block to attend (``layer``, a traced int32 scalar) passed beside them;
the kernel wants GQA-grouped query rows ``[B, KV, C*G, hd]`` (all of a KV
head's queries stream against each fetched page) and the (lengths,
q_positions) ints packed into one ``[B, 1+C, 1]`` operand.  The wrapper
reshapes the queries at the boundary — XLA fuses the transposes with the
surrounding projections on TPU — and never the pools, which the kernel
reads in place.  Quantized pools' stacked ``[R, P+1, ps]`` scale sidecars
are cut to the one layer here, before the kernel widens them.

``interpret=None`` (the default) resolves per backend: compiled on TPU,
interpreted elsewhere (CPU validation) — an explicit bool forces it, so
the fused path is never silently interpreted on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(
    q: jax.Array,  # [B, C, H, hd] (C=1 for decode)
    pool_k: jax.Array,  # [R, P+1, ps, KV*hd] (row P = garbage page)
    pool_v: jax.Array,
    layer: jax.Array,  # int32 scalar: the block of the stack to attend
    table: jax.Array,  # [B, pps] int32
    q_positions: jax.Array,  # [B, C] int32
    lengths: jax.Array,  # [B] int32 ring anchor (last written position)
    *,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [R, P+1, ps] f16 (quantized pool)
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, C, H, hd = q.shape
    KV = pool_k.shape[3] // hd
    G = H // KV
    q_r = (
        q.reshape(B, C, KV, G, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, KV, C * G, hd)
    )
    posinfo = jnp.concatenate(
        [lengths[:, None].astype(jnp.int32), q_positions.astype(jnp.int32)],
        axis=1,
    )[..., None]
    layer = jnp.asarray(layer, jnp.int32)
    if k_scale is not None:
        k_scale, v_scale = (
            jax.lax.dynamic_index_in_dim(s, layer, 0, keepdims=False)
            for s in (k_scale, v_scale)
        )
    o = paged_attention_pallas(
        q_r, pool_k, pool_v, layer.reshape(1), table, posinfo,
        pool_ks=k_scale, pool_vs=v_scale,
        window=window, interpret=interpret,
    )
    return (
        o.reshape(B, KV, C, G, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, C, H, hd)
    )
