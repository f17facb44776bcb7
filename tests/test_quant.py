"""Quantized byte streams: codec properties, fused-dequant kernel parity,
and engine-level greedy parity + byte accounting.

Layers of coverage, mirroring the three quantized streams:

  (a) codec properties (hypothesis-or-grid): int8/fp8 roundtrip error
      bounds, exact zero rows, scale linearity under power-of-two row
      scaling, and the scale-rounded-before-quantize inverse contract;
  (b) kernel-vs-ref parity in interpret mode for the quant ops, the
      paged-attention fused-dequant variant (against the dequantized-pool
      dense oracle), and the int8-slab resident expert FFN;
  (c) engine: greedy decode with all quant flags on stays within the
      documented exact-match tolerance of the f32 path at splits 0/mid/R,
      boundary bytes shrink to <= 0.55x, pools report >= 1.9x effective
      capacity, quantized pages spill/restore bit-identically through
      preemption, and byte metering is dtype-aware end to end
      (``serving.common.element_bytes`` — no hardcoded ``* 4``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.configs import get_config, smoke_config
from repro.core import compression as comp
from repro.core import expertpool
from repro.core.hardware import PROFILES
from repro.kernels.expert_mlp.ops import expert_mlp
from repro.kernels.expert_mlp.ref import expert_mlp_resident_quant_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.quant import (
    dequantize_rows,
    dequantize_rows_ref,
    quantize_rows,
    quantize_rows_ref,
)
from repro.models import kvcache
from repro.models.kvcache import PagePool
from repro.models.model import build_model
from repro.serving.common import Request, element_bytes
from repro.serving.stream import EndCloudServingEngine


# ----------------------------------------------------- (a) codec properties


@settings(max_examples=24, deadline=None)
@given(
    mode=st.sampled_from(["int8", "fp8"]),
    rows=st.integers(min_value=1, max_value=17),
    cols=st.integers(min_value=2, max_value=96),
    seed=st.integers(min_value=0, max_value=3),
)
def test_roundtrip_error_bound(mode, rows, cols, seed):
    """int8: per-element error <= scale/2 (round-to-nearest on a uniform
    grid, fp32 scale).  fp8 (e4m3): relative-precision ladder — error <=
    |x| * 2^-4 plus one subnormal step of the scaled grid."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(
        rng.standard_normal((rows, cols)) * 10.0 ** rng.integers(-3, 3),
        jnp.float32,
    )
    q, s = quantize_rows_ref(x, mode=mode)
    assert q.dtype == jnp.int8 and s.shape == (rows, 1)
    xh = dequantize_rows_ref(q, s, mode=mode, dtype=jnp.float32)
    err = jnp.abs(xh - x)
    sf = s.astype(jnp.float32)
    if mode == "int8":
        bound = sf / 2
    else:
        bound = jnp.abs(x) * 2.0 ** -4 + sf * 2.0 ** -9
    assert bool(jnp.all(err <= bound + 1e-12))


@settings(deadline=None)
@given(mode=st.sampled_from(["int8", "fp8"]))
def test_zero_rows_roundtrip_exact(mode):
    """All-zero rows must come back exactly zero (the scale floor keeps the
    divide finite without polluting the codes)."""
    x = jnp.zeros((5, 33), jnp.float32)
    q, s = quantize_rows_ref(x, mode=mode)
    assert bool(jnp.all(q == 0))
    xh = dequantize_rows_ref(q, s, mode=mode, dtype=jnp.float32)
    assert bool(jnp.all(xh == 0.0))


@settings(deadline=None)
@given(k=st.sampled_from([-3, -1, 2, 5]))
def test_scale_linearity_power_of_two(k):
    """Scaling a row by 2^k scales its quantization scale by exactly 2^k
    (fp32 scale; power-of-two so the fp mantissa is untouched) and leaves
    the int8 codes bit-identical."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    q0, s0 = quantize_rows_ref(x)
    q1, s1 = quantize_rows_ref(x * 2.0 ** k)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(
        np.asarray(s1), np.asarray(s0) * 2.0 ** k
    )


def test_f16_scale_rounded_before_quantize():
    """The sidecar-dtype contract: with a float16 scale the codes are
    computed against the *rounded* scale, so dequantizing with the stored
    sidecar still satisfies the scale/2 error bound (plus the f16 scale's
    own rounding, bounded by half an f16 ulp of the range)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    q, s = quantize_rows_ref(x, scale_dtype=jnp.float16)
    assert s.dtype == jnp.float16
    xh = dequantize_rows_ref(q, s, dtype=jnp.float32)
    sf = s.astype(jnp.float32)
    assert bool(jnp.all(jnp.abs(xh - x) <= sf / 2 + 1e-12))
    # the boundary codec and the KV pool use the same rounded-scale rule
    qb, sb = comp.quantize_boundary(x)
    assert sb.dtype == comp.BOUNDARY_SCALE_DTYPE
    zb = comp.dequantize_boundary(qb, sb, dtype=jnp.float32)
    assert bool(jnp.all(jnp.abs(zb - x) <= sb.astype(jnp.float32) / 2 + 1e-12))


# ------------------------------------------------- (b) kernel-vs-ref parity


def test_quant_ops_kernel_matches_ref():
    """The Pallas quantizer/dequantizer against the jnp oracle (interpret
    mode).  Scales may differ by 1 fp32 ulp (XLA divide-vs-reciprocal
    fusion), so parity is tolerance-based; codes differ by at most one grid
    step on ties."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    qr, sr = quantize_rows_ref(x)
    qk, sk = quantize_rows(x, interpret=True)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=3e-7)
    assert int(np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32)).max()) <= 1
    dr = dequantize_rows_ref(qr, sr, dtype=jnp.float32)
    dk = dequantize_rows(qk, sk, dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=3e-7, atol=1e-7)


def _quant_pool_case(lengths, *, ps=4, pps=4, num_pages=14, KV=2, hd=32,
                     seed=0):
    """Dense random pool -> (int8 pool + f16 sidecars, table) through the
    real allocator, plus the dequantized dense-equivalent oracle pool."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pool = PagePool(num_pages, ps, pps, n_slots=B)
    for b, ln in enumerate(lengths):
        pool.reserve(b, kvcache.pages_needed(int(ln) + 1, ps, pps))
        pool.map_range(b, 0, int(ln) + 1)
    table = pool.device_rows(range(B))
    kd = jnp.asarray(rng.standard_normal((num_pages + 1, ps, KV, hd)),
                     jnp.float32)
    vd = jnp.asarray(rng.standard_normal((num_pages + 1, ps, KV, hd)),
                     jnp.float32)
    kq, ks = kvcache.quantize_kv_tokens(kd)
    vq, vs = kvcache.quantize_kv_tokens(vd)
    return (kq, ks, vq, vs), table


@pytest.mark.parametrize("window", [None, 7])
def test_paged_attention_quant_kernel_vs_ref(window):
    """Fused in-VMEM dequant (scales ride the page-table indirection as a
    scalar-prefetched sidecar) == attention over the dequantized pool."""
    lengths = np.asarray([1, 5, 9, 15], np.int64)
    (kq, ks, vq, vs), table = _quant_pool_case(lengths)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((4, 1, 4, 32)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    k_deq = kvcache.dequantize_kv_pool(kq, ks, jnp.float32)
    v_deq = kvcache.dequantize_kv_pool(vq, vs, jnp.float32)
    want = paged_attention_ref(
        q, k_deq, v_deq, table, ln[:, None], ln, window=window
    )
    got_ref = paged_attention_ref(
        q, kq, vq, table, ln[:, None], ln, window=window,
        k_scale=ks, v_scale=vs,
    )
    got_kernel = paged_attention(
        q, kq, vq, table, ln[:, None], ln, window=window,
        k_scale=ks, v_scale=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_ref), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(got_kernel), np.asarray(got_ref), rtol=2e-5, atol=2e-5
    )


def test_paged_attention_quant_chunk_kernel_vs_ref():
    """C>1 prefill chunks over a quantized pool (the chunked-prefill read
    path the engines trace)."""
    C = 4
    start = np.asarray([0, 2, 6, 12])
    n_valid = np.asarray([4, 4, 4, 2])
    last = start + n_valid - 1
    (kq, ks, vq, vs), table = _quant_pool_case(last, seed=2)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((4, C, 4, 32)), jnp.float32)
    positions = jnp.asarray(start[:, None] + np.arange(C)[None, :], jnp.int32)
    ln = jnp.asarray(last, jnp.int32)
    k_deq = kvcache.dequantize_kv_pool(kq, ks, jnp.float32)
    v_deq = kvcache.dequantize_kv_pool(vq, vs, jnp.float32)
    want = paged_attention_ref(q, k_deq, v_deq, table, positions, ln)
    got = paged_attention(
        q, kq, vq, table, positions, ln,
        k_scale=ks, v_scale=vs, interpret=True,
    )
    valid_rows = np.arange(C)[None, :] < n_valid[:, None]
    np.testing.assert_allclose(
        np.asarray(got)[valid_rows], np.asarray(want)[valid_rows],
        rtol=2e-5, atol=2e-5,
    )


def test_expert_mlp_resident_quant_kernel_vs_ref():
    """int8 slab store: the kernel folds the per-output-column scales after
    each dot in VMEM; parity with the gather-dequantize-matmul oracle up to
    fp32 reassociation of the scale fold."""
    rng = np.random.default_rng(5)
    N, S, C, d, f = 6, 3, 8, 32, 64
    wi_q, wi_s = expertpool.quantize_slab(
        jnp.asarray(rng.standard_normal((N, d, f)), jnp.float32))
    wg_q, wg_s = expertpool.quantize_slab(
        jnp.asarray(rng.standard_normal((N, d, f)), jnp.float32))
    wo_q, wo_s = expertpool.quantize_slab(
        jnp.asarray(rng.standard_normal((N, f, d)), jnp.float32))
    x = jnp.asarray(rng.standard_normal((S, C, d)), jnp.float32)
    ids = jnp.asarray([0, 3, 5], jnp.int32)
    want = expert_mlp_resident_quant_ref(
        x, wi_q, wg_q, wo_q, wi_s, wg_s, wo_s, ids
    )
    got = expert_mlp(
        x, wi_q, wg_q, wo_q, resident_ids=ids,
        wi_scale=wi_s, wg_scale=wg_s, wo_scale=wo_s, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4
    )


def test_write_slabs_quant_roundtrip_error_bound():
    """Writing full-precision expert weights into an int8 store and
    dequantizing with the stored per-output-column scales reconstructs
    them within scale/2 per element."""
    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    moe_pos = [i for i, s in enumerate(cfg.layer_pattern) if s.moe][0]
    full = params["blocks"][f"pos{moe_pos}"]["moe"]
    store = expertpool.init_slab_store(cfg, 4, quantized=True)
    assert store["wi"].dtype == jnp.int8
    store = expertpool.write_slabs(store, full, [(0, 0, 1), (2, 1, 3)])
    for slab, b, e in ((0, 0, 1), (2, 1, 3)):
        for mat in ("wi", "wo"):
            src = np.asarray(full[mat][b, e], np.float32)
            s = np.asarray(store[f"{mat}_scale"][slab], np.float32)
            got = np.asarray(store[mat][slab], np.float32) * s[None, :]
            assert np.all(np.abs(got - src) <= s[None, :] / 2 + 1e-12)


def test_dense_page_bytes_is_exact_unquantized_counterpart():
    """dense_page_bytes == paged_block_bytes for an unquantized pool, and
    the quantized pool's per-page bytes come in under the 0.55x bar (f16
    per-token sidecar shared across KV heads)."""
    cfg = smoke_config(get_config("tinyllama-1.1b")).replace(num_layers=4)
    dense = kvcache.init_paged_blocks(cfg, 2, 8, 4, jnp.dtype(cfg.dtype))
    assert kvcache.paged_block_bytes(dense) == kvcache.dense_page_bytes(
        cfg, 2, 4
    )
    quant = kvcache.init_paged_blocks(
        cfg, 2, 8, 4, jnp.dtype(cfg.dtype), quantized=True
    )
    ratio = kvcache.paged_block_bytes(quant) / kvcache.dense_page_bytes(cfg, 2, 4)
    assert ratio <= 0.55
    assert 1.0 / ratio >= 1.9  # effective page capacity at the same budget


def test_expert_slab_bytes_quantized_ratio():
    """int8 slabs with per-output-column fp32 scales: >= 1.9x slabs per
    byte of budget (the gated smoke shape lands near 3.9x)."""
    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=2)
    dense = expertpool.expert_slab_bytes(cfg)
    quant = expertpool.expert_slab_bytes(cfg, quantized=True)
    assert quant / dense <= 0.55
    assert dense / quant >= 1.9


# ------------------------------------------------------------- (c) engine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = smoke_config(get_config("tinyllama-1.1b")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 500, size=int(rng.integers(4, 16))).astype(np.int32)
        for _ in range(n)
    ]


def _run_engine(model, params, prompts, max_new_tokens=8, **kw):
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, **kw,
    )
    reqs = [Request(i, p, max_new_tokens=max_new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return {r.request_id: list(r.generated) for r in reqs}, eng


@pytest.mark.parametrize("split", [0, 2, 4])
def test_engine_quant_greedy_parity_and_bytes(tiny_model, split):
    """All streams quantized: greedy tokens match the f32-path engine at
    >= 85% exact-match rate (documented tolerance — int8 KV + boundary
    perturb near-tied logits), every request completes full-length, and
    boundary bytes land at <= 0.55x with >= 1.9x KV page capacity."""
    model, params = tiny_model
    prompts = _prompts(6)
    base, eb = _run_engine(model, params, prompts, force_split=split)
    got, eq = _run_engine(
        model, params, prompts, force_split=split,
        quantize_kv=True, quantize_boundary=True, quantize_experts=True,
    )
    assert all(len(got[k]) == len(base[k]) for k in base)
    total = sum(len(v) for v in base.values())
    match = sum(a == b for k in base for a, b in zip(base[k], got[k]))
    assert match / total >= 0.85
    assert eq.link.bytes_up <= 0.55 * eb.link.bytes_up
    mq = eq.metrics()
    assert mq["kv_quantized"] == 1.0 and mq["boundary_quantized"] == 1.0
    assert mq["kv_capacity_ratio"] >= 1.9
    # dense baselines are priced at the dense dtype: identical across runs
    mb = eb.metrics()
    assert mq["kv_bytes_dense_equiv"] == mb["kv_bytes_dense_equiv"]
    assert mq["attn_bytes_dense_step"] == mb["attn_bytes_dense_step"]


def test_engine_quant_off_is_bit_identical(tiny_model):
    """The flags default off and the dense path stays the exact oracle:
    two quant-off runs produce bit-identical token streams and the pools
    carry no sidecar leaves."""
    model, params = tiny_model
    prompts = _prompts(6)
    a, ea = _run_engine(model, params, prompts, force_split=2)
    b, _ = _run_engine(model, params, prompts, force_split=2)
    assert a == b
    assert not any(
        "k_scale" in e for e in jax.tree.leaves(
            ea._end_pages, is_leaf=lambda x: isinstance(x, dict))
        if isinstance(e, dict)
    )


def test_engine_quant_moe_expert_stream(tiny_model):
    """MoE lane with the int8 slab store: decode completes, wire pricing
    and capacity use the stored slab size, dense baselines do not shrink."""
    cfg = smoke_config(get_config("llama4-scout-17b-16e")).replace(num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _prompts(4)
    base, eb = _run_engine(model, params, prompts, force_split=1)
    got, eq = _run_engine(
        model, params, prompts, force_split=1,
        quantize_kv=True, quantize_boundary=True, quantize_experts=True,
    )
    assert all(len(got[k]) == len(base[k]) for k in base)
    mb, mq = eb.metrics(), eq.metrics()
    assert mq["expert_quantized"] == 1.0
    assert mq["expert_slab_bytes"] <= 0.55 * mq["expert_slab_bytes_dense"]
    assert mq["expert_capacity_ratio"] >= 1.9
    # the dense-sweep baseline holds full-precision weights in both runs
    assert mq["expert_bytes_step_dense"] == mb["expert_bytes_step_dense"]
    assert mq["expert_slab_bytes_dense"] == mb["expert_slab_bytes_dense"]
    # the store itself is int8 with scale sidecars
    assert eq._slab_store["wi"].dtype == jnp.int8
    assert "wi_scale" in eq._slab_store


def _preempt_scenario_prompts():
    rng = np.random.default_rng(42)
    return [
        rng.integers(0, 500, size=n).astype(np.int32)
        for n in (12, 14, 9)
    ]


def test_quant_spill_restore_bit_identical(tiny_model):
    """A quantized-KV slot preempted mid-decode resumes bit-identically:
    the spilled pytree carries the int8 codes AND the f16 scale sidecars,
    and the restored stream matches an uninterrupted quantized run."""
    model, params = tiny_model
    pa1, pa2, pb = _preempt_scenario_prompts()
    mk = dict(quantize_kv=True, quantize_boundary=True)
    # uninterrupted quantized reference (everything fits, no preemption)
    want, _ = _run_engine(
        model, params, [pa1, pa2, pb], max_new_tokens=12,
        force_split=2, **mk,
    )
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=2, max_len=64, force_split=2,
        admission="priority", preemption=True, **mk,
    )
    a1 = Request(0, pa1, max_new_tokens=12, priority=2)
    a2 = Request(1, pa2, max_new_tokens=12, priority=2)
    b = Request(2, pb, max_new_tokens=12, priority=0)
    eng.submit(a1)
    eng.submit(a2)
    for _ in range(200):
        eng.step()
        if len(a1.generated) >= 3 and len(a2.generated) >= 3:
            break
    assert not a1.done and not a2.done
    eng.submit(b)
    eng.step()
    assert eng.n_preemptions == 1
    # the spill carries the quantized pages and their sidecars byte-exact
    (spill,) = eng._spilled.values()
    dtypes = {l.dtype for l in jax.tree.leaves(spill.blocks)}
    assert jnp.dtype(jnp.int8) in dtypes
    assert jnp.dtype(kvcache.KV_SCALE_DTYPE) in dtypes
    done = eng.run()
    assert len(done) == 3 and eng.n_preempt_restores == 1
    got = {r.request_id: list(r.generated) for r in (a1, a2, b)}
    assert got == want


def test_element_bytes_and_dtype_aware_metering(tiny_model):
    """Satellite regression: serving byte metering resolves element widths
    from dtypes.  Unit: bf16/int8 are half/quarter of f32.  Engine: the
    same workload meters exactly 2x the boundary bytes at f32 vs bf16."""
    assert element_bytes(jnp.float32) == 4
    assert element_bytes("bfloat16") == 2
    assert element_bytes(jnp.int8) == 1
    assert element_bytes(np.float16) == 2
    model16, params16 = tiny_model
    cfg32 = model16.cfg.replace(dtype="float32")
    model32 = build_model(cfg32)
    params32 = model32.init(jax.random.PRNGKey(0))
    prompts = _prompts(4)
    _, e16 = _run_engine(model16, params16, prompts, force_split=2)
    _, e32 = _run_engine(model32, params32, prompts, force_split=2)
    assert e32.link.bytes_up == 2 * e16.link.bytes_up
