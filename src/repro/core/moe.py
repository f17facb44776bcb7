"""Group-gated Mixture-of-Experts layer (HL-GGN routing + EC2MoE dispatch).

Four execution paths, selected by ``cfg.moe_impl`` (or automatically):

  * ``naive``  — loop over experts, mask-and-sum.  O(E) compute; the oracle.
  * ``sorted`` — single-shard dropless grouped GEMM: argsort assignments by
                 expert, ``jax.lax.ragged_dot``, scatter-combine.
  * ``a2a``    — paper-faithful expert parallelism: tokens are de-replicated
                 across the model axis, assignments are packed into fixed
                 per-destination capacity buffers, exchanged with
                 ``all_to_all`` (optionally LOW-RANK COMPRESSED, eq. 8),
                 computed by the owning shard, and returned.  Stage-1 of the
                 group gate selects groups == shards, so ``group_top_k``
                 directly bounds dispatch fan-out — the end-cloud insight
                 mapped onto the ICI.
  * ``tp``     — replicated-activation EP: every model shard selects the
                 assignments that hit its local experts from the (model-axis
                 replicated) activations, computes, and psums.  No all-to-all;
                 comm is one [t, d] all-reduce like a Megatron TP FFN.

All paths share the same parameters and the same HL-GGN gate, and agree
numerically when no tokens are dropped (property-tested).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compression as comp
from repro.core import gating
from repro.distributed.topology import Topology
from repro.models.layers import ACTIVATIONS, truncated_normal_init


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(key, cfg, dtype=None) -> Dict:
    m = cfg.moe
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    kg, ki, kgt, ko, ks, kc = jax.random.split(key, 6)
    p = {
        "gate": gating.init_group_gate(kg, d, m, jnp.float32),
        "wi": truncated_normal_init(ki, (E, d, f), dtype, 1.0),
        "wo": truncated_normal_init(ko, (E, f, d), dtype, 1.0),
    }
    if cfg.ffn_gated:
        p["wg"] = truncated_normal_init(kgt, (E, d, f), dtype, 1.0)
    if m.shared_experts:
        from repro.models.layers import init_mlp

        p["shared"] = init_mlp(
            ks, d, m.shared_experts * f, dtype, gated=cfg.ffn_gated
        )
    if _dispatch_compressed(cfg):
        p["codec"] = comp.init_lowrank_1d(kc, d, cfg.compression.rank, jnp.float32)
    return p


def _dispatch_compressed(cfg) -> bool:
    c = cfg.compression
    return c is not None and c.rank > 0 and "dispatch" in c.boundaries


def _capacity(n_assign: int, buckets: int, factor: float) -> int:
    c = int(-(-n_assign * factor // buckets))  # ceil
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


# ---------------------------------------------------------------------------
# Expert FFN on grouped (sorted) tokens
# ---------------------------------------------------------------------------


def _grouped_mlp(
    xs: jax.Array,  # [n, d] sorted by expert
    group_sizes: jax.Array,  # [E] int32
    wi: jax.Array,  # [E, d, f]
    wg: Optional[jax.Array],
    wo: jax.Array,  # [E, f, d]
    act: str,
) -> jax.Array:
    a = ACTIVATIONS[act]
    h = jax.lax.ragged_dot(xs, wi.astype(xs.dtype), group_sizes)
    if wg is not None:
        h = a(h) * jax.lax.ragged_dot(xs, wg.astype(xs.dtype), group_sizes)
    else:
        h = a(h)
    return jax.lax.ragged_dot(h, wo.astype(xs.dtype), group_sizes)


def _sorted_expert_ffn(
    x_rows: jax.Array,  # [n, d] unsorted assignment payloads
    eid: jax.Array,  # [n] int32 expert of each row
    num_experts: int,
    params: Dict,
    act: str,
) -> jax.Array:
    """Sort rows by expert, grouped-GEMM, unsort.  Returns [n, d]."""
    order = jnp.argsort(eid)
    gs = jnp.bincount(eid, length=num_experts).astype(jnp.int32)
    y_sorted = _grouped_mlp(
        x_rows[order], gs, params["wi"], params.get("wg"), params["wo"], act
    )
    return jnp.zeros_like(y_sorted).at[order].set(y_sorted)


# ---------------------------------------------------------------------------
# naive / sorted single-shard paths
# ---------------------------------------------------------------------------


def moe_naive(params: Dict, x: jax.Array, cfg, expert_mask=None):
    """Oracle: every expert evaluates every token; combine by gate weight."""
    m = cfg.moe
    T = x.shape[0]
    with jax.named_scope("gate"):
        out = gating.gate(params["gate"], x, m, expert_mask)
    cw = jnp.zeros((T, m.num_experts), jnp.float32)
    cw = cw.at[jnp.arange(T)[:, None], out.topk_idx].set(
        out.topk_weight.astype(jnp.float32)
    )
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(m.num_experts):
        pe = {
            "wi": params["wi"][e],
            "wo": params["wo"][e],
        }
        h = x @ pe["wi"].astype(x.dtype)
        a = ACTIVATIONS[cfg.act]
        if "wg" in params:
            h = a(h) * (x @ params["wg"][e].astype(x.dtype))
        else:
            h = a(h)
        ye = h @ pe["wo"].astype(x.dtype)
        y = y + cw[:, e : e + 1] * ye.astype(jnp.float32)
    return y.astype(x.dtype), out.aux


def moe_sorted(params: Dict, x: jax.Array, cfg, expert_mask=None):
    """Single-shard dropless path (also the oracle for the EP paths).

    When a dispatch codec is configured, the payload goes through the same
    encode -> (wire) -> decode roundtrip the EP path would apply, so the
    compression's quality effect is observable on one device and the eq. 8
    reconstruction term lands in ``aux["recon_loss"]`` for joint training.
    """
    m = cfg.moe
    T, d = x.shape
    k = m.top_k
    with jax.named_scope("gate"):
        out = gating.gate(params["gate"], x, m, expert_mask)
    flat_e = out.topk_idx.reshape(-1)  # [T*k]
    tok = jnp.arange(T * k) // k
    rows = x[tok]
    aux = dict(out.aux)
    codec = params.get("codec")
    if codec is not None:
        sent = comp.roundtrip_1d(codec, rows).astype(x.dtype)
        aux["recon_loss"] = comp.recon_loss(rows, sent)
        rows = sent
    with jax.named_scope("experts"):
        y_rows = _sorted_expert_ffn(rows, flat_e, m.num_experts, params, cfg.act)
    if codec is not None:
        back = comp.roundtrip_1d(codec, y_rows).astype(y_rows.dtype)
        aux["recon_loss"] = aux["recon_loss"] + comp.recon_loss(y_rows, back)
        y_rows = back
        c = cfg.compression
        aux["aux_loss"] = aux["aux_loss"] + c.recon_weight * aux["recon_loss"]
    w = out.topk_weight.reshape(-1, 1).astype(y_rows.dtype)
    y = jax.ops.segment_sum(y_rows * w, tok, num_segments=T)
    return y.astype(x.dtype), aux


def moe_resident(params: Dict, x: jax.Array, cfg, expert_mask=None):
    """Pooled end-tier path: sorted dispatch over the *resident* sub-table.

    ``params["resident"]`` carries the expert pool's device view
    (``core.expertpool``): ``store`` — slab storage ``[N + 1, ...]`` per
    weight matrix (last row = zero garbage slab), ``ids [S + 1]`` — the
    layer's resident slot -> physical slab gather index, ``slot [E]`` —
    expert id -> resident slot with non-residents mapped to the garbage
    slot ``S``.  The effective routing mask is computed in-trace as
    ``expert_mask AND (slot < S)``, so non-resident experts are routed
    away exactly as eq. 4-masked experts are on the dense path, and the
    weight gather reads only resident slab rows: compute and HBM traffic
    scale with residents, not ``E``.  For any resident superset of the
    routed experts this is bit-identical to ``moe_sorted`` under the same
    mask (greedy-parity-tested through the serving engines)."""
    m = cfg.moe
    T, d = x.shape
    k = m.top_k
    res = params["resident"]
    ids, slot_of = res["ids"], res["slot"]
    S = ids.shape[0] - 1
    resident_ok = slot_of < S  # [E] in-trace residency mask
    if expert_mask is not None:
        eff_mask = jnp.logical_and(jnp.asarray(expert_mask, bool), resident_ok)
    else:
        eff_mask = resident_ok
    with jax.named_scope("gate"):
        out = gating.gate(params["gate"], x, m, eff_mask)
    flat_e = out.topk_idx.reshape(-1)  # [T*k]
    slots = slot_of[flat_e]  # [T*k] -> garbage slot S for non-residents
    tok = jnp.arange(T * k) // k
    rows = x[tok]
    aux = dict(out.aux)
    codec = params.get("codec")
    if codec is not None:
        sent = comp.roundtrip_1d(codec, rows).astype(x.dtype)
        aux["recon_loss"] = comp.recon_loss(rows, sent)
        rows = sent
    with jax.named_scope("experts"):
        # gather ONLY the resident slabs (plus the shared zero garbage row)
        store = res["store"]
        wi = store["wi"][ids]  # [S+1, d, f]
        wg = store["wg"][ids] if "wg" in store else None
        wo = store["wo"][ids]  # [S+1, f, d]
        if "wi_scale" in store:
            # int8 slab store: the HBM gather reads int8 codes; dequantize
            # just the S+1 gathered slabs (per-output-column fp32 scales,
            # exact modulo the int8 grid) before the grouped GEMM
            wi = wi.astype(jnp.float32) * store["wi_scale"][ids][:, None, :]
            wo = wo.astype(jnp.float32) * store["wo_scale"][ids][:, None, :]
            if wg is not None:
                wg = wg.astype(jnp.float32) * store["wg_scale"][ids][:, None, :]
        order = jnp.argsort(slots)
        gs = jnp.bincount(slots, length=S + 1).astype(jnp.int32)
        y_sorted = _grouped_mlp(rows[order], gs, wi, wg, wo, cfg.act)
        y_rows = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
    if codec is not None:
        back = comp.roundtrip_1d(codec, y_rows).astype(y_rows.dtype)
        aux["recon_loss"] = aux["recon_loss"] + comp.recon_loss(y_rows, back)
        y_rows = back
        c = cfg.compression
        aux["aux_loss"] = aux["aux_loss"] + c.recon_weight * aux["recon_loss"]
    w = out.topk_weight.reshape(-1, 1).astype(y_rows.dtype)
    # non-resident dispatches hit the zero garbage slab; zero their combine
    # weight too so renormalized ties can never leak garbage-slab output
    w = jnp.where((slots < S)[:, None], w, 0.0)
    y = jax.ops.segment_sum(y_rows * w, tok, num_segments=T)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel paths (inside shard_map)
# ---------------------------------------------------------------------------


def _scatter_to_buckets(payload, dst, slot, capacity, n_buckets):
    """payload [n, d]; dst/slot [n] -> [n_buckets, capacity, d] with
    out-of-capacity rows dropped."""
    slot_c = jnp.minimum(slot, capacity)  # overflow parked in pad row
    buf = jnp.zeros((n_buckets, capacity + 1, payload.shape[-1]), payload.dtype)
    buf = buf.at[dst, slot_c].set(payload)
    return buf[:, :capacity]


def _scatter_meta(meta, dst, slot, capacity, n_buckets, fill=0):
    slot_c = jnp.minimum(slot, capacity)
    buf = jnp.full((n_buckets, capacity + 1), fill, meta.dtype)
    buf = buf.at[dst, slot_c].set(meta)
    return buf[:, :capacity]


def _rank_in_bucket(dst: jax.Array, n_buckets: int) -> jax.Array:
    """dst: [n] -> rank of each element among those with the same dst."""
    oh = jax.nn.one_hot(dst, n_buckets, dtype=jnp.int32)
    return (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(dst.shape[0]), dst]


def _moe_a2a_body(
    x: jax.Array,  # [t, d] dp-local, model-replicated
    experts: Dict,  # {"wi": [E_loc, d, f], ("wg"), "wo"} — LOCAL shard slices
    gate_params: Dict,  # replicated
    codec: Optional[Dict],  # replicated (or None)
    cfg,
    topo: Topology,
    expert_mask,
    capacity_factor: float,
    pre_sharded: bool = False,
):
    m = cfg.moe
    ep = topo.ep_size
    axis = topo.model_axis
    E_loc = m.num_experts // ep
    t, d = x.shape
    me = jax.lax.axis_index(axis)
    k = m.top_k

    if pre_sharded:
        # tokens already S-sharded over the model axis (sequence-parallel
        # residual stream): every local row is ours.
        ts = t
        xs = x
    else:
        # De-replicate: this shard owns tokens [me*ts, (me+1)*ts).
        ts = t // ep
        xs = jax.lax.dynamic_slice_in_dim(x, me * ts, ts, 0)
    out = gating.gate(gate_params, xs, m, expert_mask)
    eid = out.topk_idx.reshape(-1)  # [ts*k]
    w = out.topk_weight.reshape(-1)
    dst = eid // E_loc
    tok = jnp.arange(ts * k) // k
    slot = _rank_in_bucket(dst, ep)
    C = _capacity(ts * k, ep, capacity_factor)
    keep = slot < C
    dropped = 1.0 - keep.mean()

    payload = xs[tok]  # [ts*k, d]
    if codec is not None:
        payload = comp.encode_1d(codec, payload).astype(x.dtype)
    send = _scatter_to_buckets(payload, dst, slot, C, ep)
    send_eid = _scatter_meta((eid % E_loc).astype(jnp.int32), dst, slot, C, ep)

    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)  # [ep, C, dpay]
    recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0, tiled=True)

    rows = recv.reshape(ep * C, -1)
    if codec is not None:
        rows = comp.decode_1d(codec, rows).astype(x.dtype)
    y_rows = _sorted_expert_ffn(rows, recv_eid.reshape(-1), E_loc, experts, cfg.act)
    if codec is not None:
        y_rows = comp.encode_1d(codec, y_rows).astype(x.dtype)
    back = jax.lax.all_to_all(y_rows.reshape(ep, C, -1), axis, 0, 0, tiled=True)

    got = back[dst, jnp.minimum(slot, C - 1)]  # [ts*k, dpay]
    if codec is not None:
        got = comp.decode_1d(codec, got).astype(x.dtype)
    got = jnp.where(keep[:, None], got * w[:, None].astype(got.dtype), 0.0)
    y = jax.ops.segment_sum(got, tok, num_segments=ts).astype(x.dtype)

    if not pre_sharded:
        y = jax.lax.all_gather(y, axis, axis=0, tiled=True)  # [t, d]
    aux = {kk: _pmean_all(vv, topo) for kk, vv in out.aux.items()}
    aux["dropped_frac"] = _pmean_all(dropped, topo)
    return y, aux


def _moe_tp_body(
    x: jax.Array,  # [t, d] dp-local, model-replicated
    experts: Dict,  # local expert slices
    gate_params: Dict,
    codec: Optional[Dict],
    cfg,
    topo: Topology,
    expert_mask,
    capacity_factor: float,
):
    m = cfg.moe
    ep = topo.ep_size
    axis = topo.model_axis
    E_loc = m.num_experts // ep
    t, d = x.shape
    k = m.top_k
    me = jax.lax.axis_index(axis)

    out = gating.gate(gate_params, x, m, expert_mask)  # replicated compute
    eid = out.topk_idx.reshape(-1)  # [t*k]
    w = out.topk_weight.reshape(-1)
    tok = jnp.arange(t * k) // k
    mine = (eid // E_loc) == me
    # Rank among my local assignments.
    slot = jnp.cumsum(mine.astype(jnp.int32)) - 1
    C = _capacity(t * k, ep, capacity_factor)
    keep = mine & (slot < C)
    dropped = 1.0 - _pmean_all(keep.sum() / (t * k), topo) * ep

    idx = jnp.where(keep, slot, C)  # pad row
    sel_tok = jnp.full((C + 1,), 0, jnp.int32).at[idx].set(tok.astype(jnp.int32))
    sel_eid = jnp.full((C + 1,), 0, jnp.int32).at[idx].set(
        (eid % E_loc).astype(jnp.int32)
    )
    sel_w = jnp.zeros((C + 1,), jnp.float32).at[idx].set(
        jnp.where(keep, w, 0.0).astype(jnp.float32)
    )
    sel_tok, sel_eid, sel_w = sel_tok[:C], sel_eid[:C], sel_w[:C]

    xs = x[sel_tok]  # [C, d] local gather
    y_rows = _sorted_expert_ffn(xs, sel_eid, E_loc, experts, cfg.act)
    y = jax.ops.segment_sum(
        y_rows * sel_w[:, None].astype(y_rows.dtype), sel_tok, num_segments=t
    )
    if codec is not None:
        # Compressed all-reduce: the codec is linear, so summing in the
        # low-rank space commutes with decoding — psum bytes shrink by r/d.
        y = comp.decode_1d(codec, jax.lax.psum(comp.encode_1d(codec, y), axis))
        y = y.astype(x.dtype)
    else:
        y = jax.lax.psum(y.astype(jnp.float32), axis).astype(x.dtype)
    aux = {kk: _pmean_all(vv, topo) for kk, vv in out.aux.items()}
    aux["dropped_frac"] = dropped
    return y, aux


def _pmean_all(v, topo: Topology):
    names = tuple(topo.data_axes) + ((topo.model_axis,) if topo.model_axis else ())
    return jax.lax.pmean(v, names)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def apply_moe(
    params: Dict,
    x: jax.Array,  # [B, S, d] (or [T, d])
    cfg,
    topo: Optional[Topology] = None,
    *,
    expert_mask: Optional[jax.Array] = None,
    train: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The MoE layer, every operation of it under the ``moe`` name scope
    (``moe/gate`` for routing, ``moe/experts`` for the expert FFNs on the
    single-shard paths), so a profile finds its device time by name."""
    with jax.named_scope("moe"):
        return _apply_moe(params, x, cfg, topo, expert_mask=expert_mask,
                          train=train)


def _apply_moe(params, x, cfg, topo, *, expert_mask, train):
    m = cfg.moe
    impl = cfg.moe_impl
    if impl == "auto":
        impl = "a2a" if (topo is not None and topo.use_shard_map_moe) else "sorted"
    cf = m.capacity_factor if train else m.eval_capacity_factor

    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    T = x2.shape[0]

    if "resident" in params:
        # pooled end tier (paged expert weights): single-shard dispatch over
        # the resident slab sub-table, non-residents masked in-trace
        y, aux = moe_resident(params, x2, cfg, expert_mask)
        if m.shared_experts and "shared" in params:
            from repro.models.layers import apply_mlp

            y = y + apply_mlp(params["shared"], x2, cfg.act)
        return y.reshape(shape), aux

    if impl in ("a2a", "tp") and topo is not None and topo.use_shard_map_moe:
        # Decode-shape degeneracies: tiny token counts can't be de-replicated
        # across the model axis (a2a) or even sharded across data (both).
        dp, ep = topo.dp_size, topo.ep_size
        batch_shardable = T % dp == 0
        t_loc = T // dp if batch_shardable else T
        if impl == "a2a" and t_loc % ep != 0:
            impl = "tp"
        # Sequence-parallel residuals: tokens arrive S-sharded over the
        # model axis -> a2a dispatch without de-replication or output AG.
        pre_sharded = (
            topo.seq_parallel_attn
            and batch_shardable
            and t_loc % ep == 0
            and impl == "a2a"
        )
        body = _moe_a2a_body if impl == "a2a" else _moe_tp_body
        if pre_sharded:
            dp_spec = P(tuple(topo.data_axes) + (topo.model_axis,), None)
        else:
            dp_spec = (
                P(tuple(topo.data_axes), None) if batch_shardable else P(None, None)
            )
        experts = {kk: params[kk] for kk in ("wi", "wg", "wo") if kk in params}
        ep_spec = jax.tree.map(lambda _: P(topo.model_axis), experts)
        kwargs = dict(
            cfg=cfg, topo=topo, expert_mask=expert_mask, capacity_factor=cf
        )
        if impl == "a2a":
            kwargs["pre_sharded"] = pre_sharded
        body_p = functools.partial(body, **kwargs)
        if pre_sharded and len(shape) == 3:
            # Keep [B, S, d] into the shard_map (a global [B*S] flatten
            # would break the nested (dp, model) sharding contiguity and
            # force a full-residual regather per layer); flatten locally.
            sharded3 = P(tuple(topo.data_axes), topo.model_axis, None)

            def body3d(x3, experts_, gate_, codec_):
                bl, sl, dd = x3.shape
                y2, aux2 = body_p(x3.reshape(bl * sl, dd), experts_, gate_, codec_)
                return y2.reshape(bl, sl, dd), aux2

            fn = jax.shard_map(
                body3d,
                mesh=topo.mesh,
                in_specs=(sharded3, ep_spec, P(), P()),
                out_specs=(sharded3, P()),
                check_vma=False,
            )
            y, aux = fn(x, experts, params["gate"], params.get("codec"))
            # stay 3D: a global [B*S] flatten would break the nested
            # (dp, model) sharding again on the way out
            if m.shared_experts and "shared" in params:
                from repro.models.layers import apply_mlp

                y = y + apply_mlp(params["shared"], x, cfg.act)
            return y, aux
        else:
            fn = jax.shard_map(
                body_p,
                mesh=topo.mesh,
                in_specs=(dp_spec, ep_spec, P(), P()),
                out_specs=(dp_spec, P()),
                check_vma=False,
            )
            # Flatten batch/seq into tokens but KEEP the dp-sharded leading
            # dim: [B, S, d] -> [B*S, d] preserves dim-0 sharding.
            y, aux = fn(x2, experts, params["gate"], params.get("codec"))
    elif impl == "sorted":
        y, aux = moe_sorted(params, x2, cfg, expert_mask)
    elif impl == "naive":
        y, aux = moe_naive(params, x2, cfg, expert_mask)
    else:
        raise ValueError(f"unknown moe impl {impl!r} (topology={topo})")

    if m.shared_experts and "shared" in params:
        from repro.models.layers import apply_mlp

        y = y + apply_mlp(params["shared"], x2, cfg.act)
    return y.reshape(shape), aux
