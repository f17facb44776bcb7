"""Generic transformer stack: dense / MoE / hybrid-SSM / enc-dec / VLM.

A model is ``block_repeat`` copies of ``cfg.layer_pattern`` lowered with a
single ``jax.lax.scan`` over stacked block parameters (HLO stays small at 94
layers and 512 devices).  Heterogeneous dense caches (attention ring
buffers, SSM states) ride along as scan xs/ys.  Paged KV storage rides the
scan's carry instead, whole, with the block index: each layer writes its
tokens at ``[block, page, offset]`` and attention reads block ``block`` of
the stack, so the loop updates the pools in place and never slices a layer
out of them or stacks one back.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.moe import apply_moe, init_moe
from repro.distributed.topology import Topology
from repro.models import attention as attn
from repro.models import kvcache, ssm
from repro.models.layers import (
    apply_mlp,
    init_embedding,
    init_mlp,
    init_norm,
    rms_norm,
    truncated_normal_init,
)


def _has_ffn(spec, cfg) -> bool:
    return bool(spec.moe and cfg.moe) or cfg.d_ff > 0


def _constrain_tokens(
    x: jax.Array, topo: Optional[Topology], seq_shard: bool = False
) -> jax.Array:
    """Pin token-major activations to [B(dp), S, d] between blocks.

    Without this XLA's SPMD propagation may flip the residual stream to a
    batch-replicated / feature-sharded layout through the attention
    reshapes, turning every layer's backward into a full-batch all-reduce
    (measured: 40 x 20 GiB f32 on qwen3-14b train — see EXPERIMENTS.md
    §Perf iteration 1).

    ``seq_shard`` additionally shards S over the model axis at the block
    boundary (Megatron sequence parallelism): the per-layer TP all-reduce
    splits into reduce-scatter + all-gather at half the wire bytes, and
    norms/elementwise work shard too (§Perf iteration 2)."""
    if topo is None or topo.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import fit_batch_axes

    batch_axes = fit_batch_axes(x.shape[0], topo)
    if batch_axes is None:
        return x
    if batch_axes != tuple(topo.data_axes):
        # partial-batch sharding (B < dp degree): pin what divides
        spec = P(batch_axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(topo.mesh, spec)
        )
    seq_ax = None
    if (
        seq_shard
        and x.ndim >= 3
        and topo.model_axis
        and x.shape[1] % topo.ep_size == 0
    ):
        # the seq-parallel shard_map islands pin [B(dp), S(model), d]
        # themselves; an extra wsc here makes the partitioner flap between
        # layouts (measured: +88 GiB/step of gather-slice pairs)
        return x
    spec = P(tuple(topo.data_axes), seq_ax, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(topo.mesh, spec))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(key, cfg, spec, dtype) -> Dict:
    keys = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.d_model, dtype)}
    if spec.kind == "attn":
        p["attn"] = attn.init_attention(keys[0], cfg, dtype)
        if spec.cross_attn:
            p["norm_x"] = init_norm(cfg.d_model, dtype)
            p["cross"] = attn.init_attention(keys[1], cfg, dtype)
    else:
        p["ssm"] = ssm.init_ssm(keys[0], cfg, dtype)
    if _has_ffn(spec, cfg):
        p["norm2"] = init_norm(cfg.d_model, dtype)
        if spec.moe:
            p["moe"] = init_moe(keys[2], cfg, dtype)
        else:
            p["ffn"] = init_mlp(keys[3], cfg.d_model, cfg.d_ff, dtype, cfg.ffn_gated)
    return p


def init_params(key, cfg) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    R = cfg.block_repeat
    k_embed, k_blocks, k_head, k_enc = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embedding(k_embed, cfg.padded_vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.d_model, dtype),
    }
    blocks = {}
    pos_keys = jax.random.split(k_blocks, len(cfg.layer_pattern))
    for i, spec in enumerate(cfg.layer_pattern):
        layer_keys = jax.random.split(pos_keys[i], R)
        blocks[f"pos{i}"] = jax.vmap(
            lambda kk, spec=spec: init_layer(kk, cfg, spec, dtype)
        )(layer_keys)
    params["blocks"] = blocks
    if not cfg.tie_embeddings:
        params["lm_head"] = truncated_normal_init(
            k_head, (cfg.d_model, cfg.padded_vocab_size), dtype, 1.0
        )
    if cfg.encoder_decoder:
        from repro.configs.base import LayerSpec

        enc_spec = LayerSpec(kind="attn")
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(
                lambda kk: init_layer(kk, cfg, enc_spec, dtype)
            )(enc_keys),
            "norm": init_norm(cfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _self_attention_full(p, h, cfg, angles, causal):
    with jax.named_scope("attention"):
        q, k, v = attn.project_qkv(p, h, cfg, angles)
        o = attn.flash_attention(
            q,
            k,
            v,
            causal=causal,
            window=cfg.sliding_window if causal else None,
            q_chunk=cfg.attn_chunk_q,
            kv_chunk=cfg.attn_chunk_kv,
        )
        return attn.output_proj(p, o), (k, v)


def _self_attention_seqp(p, h, cfg, topo, angles, causal):
    """Sequence-parallel self attention (§Perf iteration on qwen3-moe):
    tokens stay S-sharded over the model axis; only the GQA K/V heads are
    all-gathered (KV*hd bytes per token instead of d), eliminating both the
    per-layer TP all-reduce and the MoE-output all-gather."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    mesh = topo.mesh
    axis = topo.model_axis
    dp = tuple(topo.data_axes)

    def body(h_loc, angles_loc, params):
        # h_loc: [B_loc, S_loc, d]
        me = jax.lax.axis_index(axis)
        S_loc = h_loc.shape[1]
        q, k, v = attn.project_qkv(params, h_loc, cfg, angles_loc)
        k_full = jax.lax.all_gather(k, axis, axis=1, tiled=True)
        v_full = jax.lax.all_gather(v, axis, axis=1, tiled=True)
        qpos = me * S_loc + jnp.arange(S_loc, dtype=jnp.int32)
        o = attn.flash_attention(
            q, k_full, v_full,
            causal=causal,
            window=cfg.sliding_window if causal else None,
            q_chunk=cfg.attn_chunk_q,
            kv_chunk=cfg.attn_chunk_kv,
            q_positions=qpos,
        )
        return attn.output_proj(params, o), (k_full, v_full)

    sharded = P(dp, axis, None)

    # caches come back S-sharded: each shard emits its LOCAL k/v slice
    def body_kv_local(h_loc, angles_loc, params):
        o, (kf, vf) = body(h_loc, angles_loc, params)
        S_loc = h_loc.shape[1]
        me = jax.lax.axis_index(axis)
        k_loc = jax.lax.dynamic_slice_in_dim(kf, me * S_loc, S_loc, 1)
        v_loc = jax.lax.dynamic_slice_in_dim(vf, me * S_loc, S_loc, 1)
        return o, (k_loc, v_loc)

    fn = jax.shard_map(
        body_kv_local,
        mesh=mesh,
        in_specs=(sharded, P(dp, axis, None), P()),
        out_specs=(sharded, (P(dp, axis, None, None), P(dp, axis, None, None))),
        check_vma=False,
    )
    return fn(h, angles, p)


def _cross_attention_full(p, h, enc_out, cfg):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(h.dtype))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    o = attn.flash_attention(
        q, k, v, causal=False,
        q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv,
    )
    return attn.output_proj(p, o), (k, v)


def apply_layer_full(
    p: Dict,
    x: jax.Array,  # [B, S, d]
    spec,
    cfg,
    topo: Optional[Topology],
    angles,
    *,
    causal: bool = True,
    enc_out=None,
    expert_mask=None,
    train: bool = True,
    collect_cache: bool = False,
    max_len: int = 0,
):
    """Full-sequence layer (train / prefill).  Returns (x, aux, cache_entry)."""
    aux: Dict[str, jax.Array] = {}
    cache_entry: Dict[str, jax.Array] = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.kind == "attn":
        use_seqp = (
            topo is not None
            and topo.mesh is not None
            and topo.seq_parallel_attn
            and not spec.cross_attn
            and x.shape[0] % topo.dp_size == 0
            and x.shape[1] % topo.ep_size == 0
        )
        if use_seqp:
            o, (k, v) = _self_attention_seqp(p["attn"], h, cfg, topo, angles, causal)
        else:
            o, (k, v) = _self_attention_full(p["attn"], h, cfg, angles, causal)
        x = x + o
        if collect_cache:
            W = kvcache.attn_cache_len(cfg, max_len)
            B = x.shape[0]
            kc = jnp.zeros((B, W, cfg.num_kv_heads, cfg.head_dim), k.dtype)
            vc = jnp.zeros_like(kc)
            cache_entry["k"], cache_entry["v"] = kvcache.prefill_write(kc, vc, k, v)
        if spec.cross_attn:
            hx = rms_norm(x, p["norm_x"], cfg.norm_eps)
            ox, (xk, xv) = _cross_attention_full(p["cross"], hx, enc_out, cfg)
            x = x + ox
            if collect_cache:
                cache_entry["xk"], cache_entry["xv"] = xk, xv
    else:
        if collect_cache:
            o, (final_state, (cx, cbc)) = ssm.apply_ssm(
                p["ssm"], h, cfg, topo=topo, return_state=True
            )
            cache_entry["ssm"] = final_state
            cache_entry["conv_x"] = cx
            cache_entry["conv_bc"] = cbc
        else:
            o = ssm.apply_ssm(p["ssm"], h, cfg, topo=topo)
        x = x + o
    if _has_ffn(spec, cfg):
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.moe:
            y, aux = apply_moe(
                p["moe"], h, cfg, topo, expert_mask=expert_mask, train=train
            )
        else:
            with jax.named_scope("mlp"):
                y = apply_mlp(p["ffn"], h, cfg.act)
        x = x + y
    return x, aux, cache_entry


def apply_layer_decode(
    p: Dict,
    x: jax.Array,  # [B, 1, d]
    spec,
    cfg,
    topo: Optional[Topology],
    angles,  # [B, 1, hd/2]
    cache_entry: Dict,
    lengths: jax.Array,  # [B]
    expert_mask=None,
    page_table: Optional[jax.Array] = None,  # [B, pps] -> paged KV layout
    page_size: int = 0,
    expert_resident: Optional[Dict] = None,  # this layer's resident tables
    layer: Optional[jax.Array] = None,  # block index into the paged stack
):
    """Single-token decode layer.  Returns (x, new_cache_entry, aux).

    With ``page_table`` set, attention ``k``/``v`` leaves are the stacked
    page pools of every block, ``[R, P+1, page_size, KV*hd]``: the write
    scatters the token into block ``layer`` through the table and attention
    reads the slot's mapped pages of that block *directly from the stack*
    (``attn.paged_decode_attention`` — page lookup, ring masking, and
    online softmax fused; no dense ring view is materialized), so HBM
    traffic scales with mapped pages while greedy decode stays
    token-identical to the dense layout."""
    aux: Dict[str, jax.Array] = {}
    new_entry = dict(cache_entry)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.kind == "attn":
        with jax.named_scope("attention"):
            q, k, v = attn.project_qkv(p["attn"], h, cfg, angles)
        if page_table is not None and "k_scale" in cache_entry:
            # int8 page pool: quantize-on-write, fused dequant in attention
            with jax.named_scope("kv_write"):
                kc, vc, ksc, vsc = kvcache.paged_ring_write_quant(
                    cache_entry["k"], cache_entry["v"],
                    cache_entry["k_scale"], cache_entry["v_scale"], k, v,
                    page_table, lengths, page_size, layer,
                )
            new_entry["k"], new_entry["v"] = kc, vc
            new_entry["k_scale"], new_entry["v_scale"] = ksc, vsc
            with jax.named_scope("attention"):
                o = attn.paged_decode_attention(
                    q, kc, vc, layer, page_table, lengths,
                    window=cfg.sliding_window, k_scale=ksc, v_scale=vsc,
                )
        elif page_table is not None:
            with jax.named_scope("kv_write"):
                kc, vc = kvcache.paged_ring_write(
                    cache_entry["k"], cache_entry["v"], k, v,
                    page_table, lengths, page_size, layer,
                )
            new_entry["k"], new_entry["v"] = kc, vc
            with jax.named_scope("attention"):
                o = attn.paged_decode_attention(
                    q, kc, vc, layer, page_table, lengths,
                    window=cfg.sliding_window,
                )
        else:
            with jax.named_scope("kv_write"):
                kc, vc = kvcache.ring_write(
                    cache_entry["k"], cache_entry["v"], k, v, lengths
                )
            new_entry["k"], new_entry["v"] = kc, vc
            with jax.named_scope("attention"):
                key_pos = kvcache.ring_key_positions(lengths, kc.shape[1])
                o = attn.decode_attention(
                    q, kc, vc, lengths, key_pos, window=cfg.sliding_window
                )
        with jax.named_scope("attention"):
            x = x + attn.output_proj(p["attn"], o)
        if spec.cross_attn:
            hx = rms_norm(x, p["norm_x"], cfg.norm_eps)
            qx = jnp.einsum("bsd,dhk->bshk", hx, p["cross"]["wq"].astype(hx.dtype))
            if cfg.qk_norm:
                qx = rms_norm(qx, p["cross"]["q_norm"], cfg.norm_eps)
            S_enc = cache_entry["xk"].shape[1]
            enc_pos = jnp.full((x.shape[0],), S_enc, jnp.int32)
            key_pos_x = jnp.broadcast_to(
                jnp.arange(S_enc)[None], (x.shape[0], S_enc)
            )
            ox = attn.decode_attention(
                qx, cache_entry["xk"], cache_entry["xv"], enc_pos, key_pos_x
            )
            x = x + attn.output_proj(p["cross"], ox)
    else:
        o, (new_ssm, (new_cx, new_cbc)) = ssm.apply_ssm_decode(
            p["ssm"], h, cfg, cache_entry["ssm"],
            (cache_entry["conv_x"], cache_entry["conv_bc"]),
        )
        new_entry["ssm"] = new_ssm
        new_entry["conv_x"], new_entry["conv_bc"] = new_cx, new_cbc
        x = x + o
    if _has_ffn(spec, cfg):
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.moe:
            mp = p["moe"]
            if expert_resident is not None:
                # pooled end tier: the stripped moe params get this layer's
                # resident tables + the shared slab store (core.expertpool)
                mp = {**mp, "resident": expert_resident}
            y, aux = apply_moe(
                mp, h, cfg, topo, expert_mask=expert_mask, train=False
            )
        else:
            with jax.named_scope("mlp"):
                y = apply_mlp(p["ffn"], h, cfg.act)
        x = x + y
    return x, new_entry, aux


# ---------------------------------------------------------------------------
# Stacks (scan over blocks)
# ---------------------------------------------------------------------------


def _merge_aux(acc: Dict, aux: Dict) -> Dict:
    for k, v in aux.items():
        acc[k] = acc.get(k, 0.0) + v
    return acc


def apply_stack_full(
    params: Dict,
    x: jax.Array,
    cfg,
    topo,
    angles,
    *,
    causal=True,
    enc_out=None,
    expert_mask=None,
    train=True,
    collect_cache=False,
    max_len=0,
    remat=True,
):
    """Scan the repeated block pattern over the sequence.  Returns
    (x, aux_sums, cache_blocks|None)."""

    def block_fn(carry_x, block_params):
        bx = carry_x
        aux_acc: Dict[str, jax.Array] = {}
        caches = {}
        seqp = cfg.seq_parallel or (topo is not None and topo.seq_parallel_attn)
        for i, spec in enumerate(cfg.layer_pattern):
            bx = _constrain_tokens(bx, topo, seq_shard=seqp)
            bx, aux, ce = apply_layer_full(
                block_params[f"pos{i}"], bx, spec, cfg, topo, angles,
                causal=causal, enc_out=enc_out, expert_mask=expert_mask,
                train=train, collect_cache=collect_cache, max_len=max_len,
            )
            aux_acc = _merge_aux(aux_acc, aux)
            if collect_cache:
                caches[f"pos{i}"] = ce
        bx = _constrain_tokens(bx, topo)
        return bx, (aux_acc, caches)

    fn = jax.checkpoint(block_fn) if (remat and train) else block_fn
    x, (aux_stack, cache_stack) = jax.lax.scan(fn, x, params["blocks"])
    # reduce over the block axis only: scalar aux stays scalar, measured
    # routing statistics (expert_frac [E] / group_frac [K]) keep their shape
    aux = {k: v.sum(axis=0) for k, v in aux_stack.items()}
    return x, aux, (cache_stack if collect_cache else None)


def _scan_paged(block_fn, x, pages, xs):
    """``lax.scan`` of ``block_fn`` over the blocks in ``xs`` with the
    paged storage and the block index in the carry.  Returns
    (x, pages, ys).

    A tier may hold no blocks (a split at 0 or at R).  The scan would still
    trace its body, which cannot index a zero-length stack, so the body is
    then only evaluated for the shapes of what it stacks, on a one-block
    stand-in, and those come back empty."""
    if jax.tree.leaves(xs)[0].shape[0]:
        (x, pages, _), ys = jax.lax.scan(
            block_fn, (x, pages, jnp.int32(0)), xs
        )
        return x, pages, ys
    sds = jax.ShapeDtypeStruct
    one = jax.tree.map(lambda a: sds((1,) + a.shape[1:], a.dtype), pages)
    xs1 = jax.tree.map(lambda a: sds(a.shape[1:], a.dtype), xs)
    _, ys = jax.eval_shape(block_fn, (x, one, jnp.int32(0)), xs1)
    return x, pages, jax.tree.map(lambda a: jnp.zeros((0,) + a.shape, a.dtype), ys)


def _decode_block(block_params, bx, entries, tab, store, cfg, topo, angles,
                  lengths, expert_mask, page_table, page_size, layer):
    """One block repeat of single-token decode: every pattern position's
    layer in turn.  Returns (x, new_entries, aux sums)."""
    new_entries = {}
    aux_acc: Dict[str, jax.Array] = {}
    for i, spec in enumerate(cfg.layer_pattern):
        res = None
        if tab is not None and spec.moe:
            res = {**tab[f"pos{i}"], "store": store}
        bx, ne, aux = apply_layer_decode(
            block_params[f"pos{i}"], bx, spec, cfg, topo, angles,
            entries[f"pos{i}"], lengths, expert_mask=expert_mask,
            page_table=page_table, page_size=page_size,
            expert_resident=res, layer=layer,
        )
        new_entries[f"pos{i}"] = ne
        aux_acc = _merge_aux(aux_acc, aux)
    return bx, new_entries, aux_acc


def apply_stack_decode(
    params: Dict,
    x: jax.Array,
    cfg,
    topo,
    angles,
    cache_blocks: Dict,
    lengths: jax.Array,
    expert_mask=None,
    *,
    page_table: Optional[jax.Array] = None,
    page_size: int = 0,
    expert_resident: Optional[Dict] = None,
):
    """``expert_resident`` (pooled end tier) is
    ``{"store": {...}, "tables": {"pos{i}": {"ids": [R, S+1], "slot":
    [R, E]}}}`` from ``core.expertpool``: per-block resident tables ride
    the scan as xs while the slab store is a loop constant, so MoE layers
    gather only resident slab rows (``core.moe.moe_resident``).

    With ``page_table`` set, ``cache_blocks`` is the paged storage stacked
    over the blocks; it rides the carry with the block index and every
    layer updates it in place (the dense ring caches ride xs/ys)."""
    tables = expert_resident["tables"] if expert_resident is not None else None
    store = expert_resident["store"] if expert_resident is not None else None
    xs = (params["blocks"],)
    if tables is not None:
        xs = xs + (tables,)

    if page_table is not None:
        def paged_block_fn(carry, xs_):
            bx, pages, blk = carry
            tab = xs_[1] if tables is not None else None
            bx, pages, aux_acc = _decode_block(
                xs_[0], bx, pages, tab, store, cfg, topo, angles, lengths,
                expert_mask, page_table, page_size, blk,
            )
            return (bx, pages, blk + 1), aux_acc

        x, new_cache, aux_stack = _scan_paged(
            paged_block_fn, x, cache_blocks, xs
        )
    else:
        def block_fn(carry_x, xs_):
            tab = xs_[2] if tables is not None else None
            bx, ne, aux_acc = _decode_block(
                xs_[0], carry_x, xs_[1], tab, store, cfg, topo, angles,
                lengths, expert_mask, None, page_size, None,
            )
            return bx, (ne, aux_acc)

        x, (new_cache, aux_stack) = jax.lax.scan(
            block_fn, x, (xs[0], cache_blocks) + xs[1:]
        )
    # reduce over the block axis only: scalar aux stays scalar, measured
    # routing statistics (expert_frac [E] / group_frac [K]) keep their shape
    aux = {k: v.sum(axis=0) for k, v in aux_stack.items()}
    return x, new_cache, aux


def apply_stack_prefill_chunk(
    params: Dict,
    x: jax.Array,  # [B, C, d] one fixed-size prompt chunk
    cfg,
    topo,
    angles,  # [B, C, hd/2]
    page_blocks: Dict,  # paged KV storage (attn-only pattern)
    page_table: jax.Array,  # [B, pps]
    positions: jax.Array,  # [B, C] absolute position of every chunk row
    n_valid: jax.Array,  # [B] rows < n_valid are real, the rest padding
    page_size: int,
    expert_mask=None,
    expert_resident: Optional[Dict] = None,
):
    """Chunked prefill over the repeated block pattern (attention-only
    patterns; the serving engines gate on ``kvcache.pattern_is_pageable``).

    Each layer writes the chunk's k/v through the page table first (padding
    rows routed to the garbage page), then attends the chunk's queries
    against the slot's mapped pages directly (``attn.paged_chunk_attention``
    — no gathered ring view) — so a prompt streams through one compiled
    trace per *chunk shape*, never one per prompt length, and the chunk
    leaves exactly the pages a whole-prompt prefill would have left.  The
    stacked storage rides the scan's carry with the block index and is
    written in place.  Returns (x [B, C, d], new_page_blocks)."""
    C = x.shape[1]
    valid = jnp.arange(C)[None, :] < n_valid[:, None]  # [B, C]
    last_pos = positions[:, 0] + n_valid - 1  # [B] final real position
    tables = expert_resident["tables"] if expert_resident is not None else None
    store = expert_resident["store"] if expert_resident is not None else None
    xs = (params["blocks"],)
    if tables is not None:
        xs = xs + (tables,)

    def block_fn(carry, xs_):
        bx, pages, blk = carry
        block_params = xs_[0]
        tab = xs_[1] if tables is not None else None
        new_entries = {}
        for i, spec in enumerate(cfg.layer_pattern):
            p = block_params[f"pos{i}"]
            ce = pages[f"pos{i}"]
            h = rms_norm(bx, p["norm1"], cfg.norm_eps)
            with jax.named_scope("attention"):
                q, k, v = attn.project_qkv(p["attn"], h, cfg, angles)
            if "k_scale" in ce:
                # int8 page pool: quantize-on-write + fused dequant
                with jax.named_scope("kv_write"):
                    kc, vc, ksc, vsc = kvcache.paged_write_tokens_quant(
                        ce["k"], ce["v"], ce["k_scale"], ce["v_scale"], k, v,
                        page_table, positions, valid, page_size, blk,
                    )
                with jax.named_scope("attention"):
                    o = attn.paged_chunk_attention(
                        q, kc, vc, blk, page_table, positions, last_pos,
                        window=cfg.sliding_window, k_scale=ksc, v_scale=vsc,
                    )
                entry_out = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
            else:
                with jax.named_scope("kv_write"):
                    kc, vc = kvcache.paged_write_tokens(
                        ce["k"], ce["v"], k, v, page_table, positions, valid,
                        page_size, blk,
                    )
                with jax.named_scope("attention"):
                    o = attn.paged_chunk_attention(
                        q, kc, vc, blk, page_table, positions, last_pos,
                        window=cfg.sliding_window,
                    )
                entry_out = {"k": kc, "v": vc}
            with jax.named_scope("attention"):
                bx = bx + attn.output_proj(p["attn"], o)
            if _has_ffn(spec, cfg):
                h = rms_norm(bx, p["norm2"], cfg.norm_eps)
                if spec.moe:
                    mp = p["moe"]
                    if tab is not None:
                        mp = {**mp, "resident": {**tab[f"pos{i}"], "store": store}}
                    y, _ = apply_moe(
                        mp, h, cfg, topo, expert_mask=expert_mask,
                        train=False,
                    )
                else:
                    with jax.named_scope("mlp"):
                        y = apply_mlp(p["ffn"], h, cfg.act)
                bx = bx + y
            new_entries[f"pos{i}"] = entry_out
        return (bx, new_entries, blk + 1), None

    x, new_blocks, _ = _scan_paged(block_fn, x, page_blocks, xs)
    return x, new_blocks


def apply_encoder(params: Dict, frame_embeds: jax.Array, cfg, topo):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    B, S, _ = frame_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    angles = attn.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = frame_embeds

    def block_fn(carry_x, block_params):
        bx, _, _ = apply_layer_full(
            block_params, carry_x,
            type(cfg.layer_pattern[0])(kind="attn"),  # plain attn spec
            cfg, topo, angles, causal=False, train=False,
        )
        return bx, None

    x, _ = jax.lax.scan(block_fn, x, params["encoder"]["blocks"])
    return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg, tokens, patch_embeds=None):
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return x


def lm_logits(params, cfg, x):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(x.dtype)
        logits = x @ head
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            logits = c * jnp.tanh(logits / c)
        if cfg.padded_vocab_size != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        return logits
