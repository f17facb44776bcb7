"""Fused paged decode/chunk attention vs the dense ``paged_gather`` oracle.

Three layers of parity, all in interpret mode (the kernel autodetects the
backend, so this file exercises exactly what CI runs on CPU):

  (a) functional: the pure-JAX gather-free ref and the Pallas kernel both
      match ``paged_gather`` + ``decode_attention``/``chunk_attention`` on
      random pools — ragged lengths (including a just-admitted slot holding
      a single token), sliding windows smaller than the ring, ring wrap,
      and C>1 prefill chunks with padding rows;
  (b) page-skip: garbage-routed and wholly-masked pages contribute nothing
      (a corrupted garbage page must not leak into live outputs);
  (c) end-to-end: greedy decode through the serving engines with the
      attention implementation pinned to the Pallas kernel is
      token-identical (f32) to the pre-refactor dense-gather oracle at tier
      splits 0 / mid / R, and through chunked prefill + sliding windows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.hardware import PROFILES
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import attention as attn
from repro.models import kvcache
from repro.models.kvcache import PagePool
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.stream import EndCloudServingEngine


# ------------------------------------------------------------ (a) functional


def _pool_case(lengths, *, ps=4, pps=4, num_pages=14, KV=2, hd=32, seed=0,
               dtype=jnp.float32, n_layers=1):
    """Random pool + tables built through the real allocator: slot b holds
    positions [0, lengths[b]] (its current decode token included), mapped
    exactly as the engines map them; untouched entries stay garbage.  The
    pools are a stack of ``n_layers`` blocks in the engines' lane-dense
    layout, ``[n_layers, P+1, ps, KV*hd]``, different in every block."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pool = PagePool(num_pages, ps, pps, n_slots=B)
    for b, ln in enumerate(lengths):
        pool.reserve(b, kvcache.pages_needed(int(ln) + 1, ps, pps))
        pool.map_range(b, 0, int(ln) + 1)
    table = pool.device_rows(range(B))
    shape = (n_layers, num_pages + 1, ps, KV * hd)
    pool_k = jnp.asarray(rng.standard_normal(shape), dtype)
    pool_v = jnp.asarray(rng.standard_normal(shape), dtype)
    return pool_k, pool_v, table


def _dense_reference(q, pool_k, pool_v, table, q_positions, lengths, window,
                     layer=0):
    """The pre-refactor path: materialize the ring of block ``layer`` via
    paged_gather, then dense masked-softmax attention."""
    W = table.shape[1] * pool_k.shape[2]
    KV = pool_k.shape[3] // q.shape[-1]
    kbuf = kvcache.paged_gather(pool_k[layer], table, KV)
    vbuf = kvcache.paged_gather(pool_v[layer], table, KV)
    key_pos = kvcache.ring_key_positions(lengths, W)
    if q.shape[1] == 1:
        return attn.decode_attention(
            q, kbuf, vbuf, lengths, key_pos, window=window
        )
    return attn.chunk_attention(
        q, kbuf, vbuf, q_positions, key_pos, window=window
    )


@pytest.mark.parametrize("window", [None, 7])
def test_decode_matches_dense_gather_oracle(window):
    """Ragged decode lengths — slot 0 was just admitted and holds exactly
    one token (its prefill token at position 0, decoding position 1)."""
    lengths = np.asarray([1, 5, 9, 15], np.int64)
    pool_k, pool_v, table = _pool_case(lengths)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((4, 1, 4, 32)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    want = _dense_reference(q, pool_k, pool_v, table, ln[:, None], ln, window)
    got_ref = paged_attention_ref(
        q, pool_k, pool_v, 0, table, ln[:, None], ln, window=window
    )
    got_kernel = paged_attention(
        q, pool_k, pool_v, 0, table, ln[:, None], ln,
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_ref), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(got_kernel), np.asarray(got_ref), rtol=2e-6, atol=2e-6
    )


@pytest.mark.parametrize("window", [None, 9])
def test_chunk_matches_chunk_attention(window):
    """C>1 prefill chunks at ragged offsets, padding rows included: slot 3's
    chunk holds only 2 valid rows (the engines route its padding writes to
    the garbage page; its padded queries are computed and discarded)."""
    C = 4
    start = np.asarray([0, 2, 6, 12])
    n_valid = np.asarray([4, 4, 4, 2])
    last = start + n_valid - 1
    pool_k, pool_v, table = _pool_case(last, seed=2)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((4, C, 4, 32)), jnp.float32)
    positions = jnp.asarray(start[:, None] + np.arange(C)[None, :], jnp.int32)
    ln = jnp.asarray(last, jnp.int32)
    want = _dense_reference(q, pool_k, pool_v, table, positions, ln, window)
    got_ref = paged_attention_ref(
        q, pool_k, pool_v, 0, table, positions, ln, window=window
    )
    got_kernel = paged_attention(
        q, pool_k, pool_v, 0, table, positions, ln,
        window=window, interpret=True,
    )
    valid_rows = np.arange(C)[None, :] < n_valid[:, None]  # [B, C]
    for got in (got_ref, got_kernel):
        np.testing.assert_allclose(
            np.asarray(got)[valid_rows], np.asarray(want)[valid_rows],
            rtol=2e-5, atol=2e-5,
        )


def test_ring_wrap_matches_dense_gather_oracle():
    """Positions past the ring capacity reuse the slot's own pages in
    place; the window mask must track the wrapped ring exactly."""
    ps, pps = 4, 4  # ring of 16 tokens
    window = 10
    lengths = np.asarray([21, 37, 16], np.int64)  # all past one wrap
    rng = np.random.default_rng(4)
    B = len(lengths)
    pool = PagePool(12, ps, pps, n_slots=B)
    for b in range(B):
        pool.reserve(b, pps)
        pool.map_range(b, 0, int(lengths[b]) + 1)
    table = pool.device_rows(range(B))
    pool_k = jnp.asarray(rng.standard_normal((1, 13, ps, 64)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((1, 13, ps, 64)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, 4, 32)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    want = _dense_reference(q, pool_k, pool_v, table, ln[:, None], ln, window)
    got = paged_attention(
        q, pool_k, pool_v, 0, table, ln[:, None], ln,
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_kernel_reads_its_layer_of_the_stack(quantized, layer):
    """The kernel attends block ``l`` of a stacked pool whose blocks all
    hold different pages, read in place through the scalar-prefetched
    layer operand, and equals the reference on that block alone: a stack
    of one block, cut out beforehand.  bf16 pools, and int8 pools with
    their per-token scale sidecars."""
    R = 3
    l = 0 if layer == "first" else R - 1
    lengths = np.asarray([1, 6, 13], np.int64)
    pool_k, pool_v, table = _pool_case(lengths, seed=11, n_layers=R)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((3, 1, 4, 32)), jnp.bfloat16)
    ln = jnp.asarray(lengths, jnp.int32)
    if quantized:
        def q8(pool):  # per-token int8 codes over the token's whole row
            return kvcache.quantize_kv_tokens(pool[..., None, :])

        (kq, ks), (vq, vs) = q8(pool_k), q8(pool_v)
        kq, vq = kq[..., 0, :], vq[..., 0, :]
        got = paged_attention(
            q, kq, vq, l, table, ln[:, None], ln,
            k_scale=ks, v_scale=vs, interpret=True,
        )
        want = paged_attention_ref(
            q, kq[l:l + 1], vq[l:l + 1], 0, table, ln[:, None], ln,
            k_scale=ks[l:l + 1], v_scale=vs[l:l + 1],
        )
    else:
        pool_k, pool_v = (p.astype(jnp.bfloat16) for p in (pool_k, pool_v))
        got = paged_attention(
            q, pool_k, pool_v, l, table, ln[:, None], ln, interpret=True
        )
        want = paged_attention_ref(
            q, pool_k[l:l + 1], pool_v[l:l + 1], 0, table, ln[:, None], ln
        )
    other = paged_attention_ref(
        q, pool_k, pool_v, (l + 1) % R, table, ln[:, None], ln,
        **({"k_scale": ks, "v_scale": vs} if quantized else {}),
    )
    got, want, other = (np.asarray(a, np.float32) for a in (got, want, other))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.abs(got - other).max() > 0.1  # another block reads otherwise


@pytest.mark.parametrize(
    "H,KV,hd",
    [(6, 6, 64), (8, 4, 32), (6, 3, 128), (8, 2, 128)],
    ids=["pairs", "one-tile", "wide-heads", "gqa-wide"],
)
def test_head_groups_match_the_reference(H, KV, hd):
    """The kernel folds each 128-lane tile's heads into one block-diagonal
    matmul (pairs of 64-wide heads, a row of four 32-wide heads in one
    tile) or takes heads one at a time (128 wide); every layout equals
    the reference on a two-query chunk with GQA rows."""
    C = 2
    last = np.asarray([1, 6, 14], np.int64)
    pool_k, pool_v, table = _pool_case(last, KV=KV, hd=hd, seed=21)
    rng = np.random.default_rng(22)
    q = jnp.asarray(rng.standard_normal((3, C, H, hd)), jnp.float32)
    positions = jnp.asarray(last[:, None] - C + 1 + np.arange(C), jnp.int32)
    positions = jnp.maximum(positions, 0)
    ln = jnp.asarray(last, jnp.int32)
    want = paged_attention_ref(q, pool_k, pool_v, 0, table, positions, ln)
    got = paged_attention(
        q, pool_k, pool_v, 0, table, positions, ln, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------------- (b) page skip


def test_garbage_pages_contribute_nothing():
    """Poisoning the garbage page must not change any live output — the
    kernel skips garbage-routed entries instead of masking post-hoc — and a
    slot whose table is ALL garbage (inactive) comes back exactly zero."""
    lengths = np.asarray([3, 9], np.int64)
    pool_k, pool_v, table = _pool_case(lengths, seed=5)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 32)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    base = paged_attention(
        q, pool_k, pool_v, 0, table, ln[:, None], ln, interpret=True
    )
    poisoned_k = pool_k.at[:, -1].set(1e4)
    poisoned_v = pool_v.at[:, -1].set(1e4)
    got = paged_attention(
        q, poisoned_k, poisoned_v, 0, table, ln[:, None], ln, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))

    all_garbage = jnp.full_like(table, pool_k.shape[1] - 1)
    zero = paged_attention(
        q, poisoned_k, poisoned_v, 0, all_garbage, ln[:, None], ln,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(zero), 0.0)


# ------------------------------------------------- (c) end-to-end greedy


@pytest.fixture(scope="module")
def tiny_model_f32():
    cfg = (
        smoke_config(get_config("tinyllama-1.1b"))
        .replace(num_layers=4, dtype="float32")
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture()
def kernel_impl():
    """Pin paged attention to the Pallas kernel (interpret mode on CPU) for
    the duration of a test.  Impl choice is read at trace time, so each
    test builds its engines inside the fixture's scope."""
    attn.set_paged_attention_impl("kernel")
    yield
    attn.set_paged_attention_impl(None)


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


@pytest.mark.parametrize("split", [0, 2, 4])
def test_greedy_token_parity_kernel_vs_dense_oracle(
    tiny_model_f32, kernel_impl, split
):
    """The acceptance bar: greedy decode through the fused Pallas kernel
    (both tiers, chunked prefill included) is token-identical in f32 to the
    dense paged_gather oracle at splits 0 / mid / R."""
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


def test_sliding_window_greedy_parity_kernel(kernel_impl):
    """window < max_len: the ring wraps during prefill AND decode; kernel
    greedy tokens must still match the dense whole-prompt path (f32)."""
    cfg = (
        smoke_config(get_config("tinyllama-1.1b"))
        .replace(num_layers=2, dtype="float32", sliding_window=24)
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
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


def test_kernel_and_ref_impls_agree_end_to_end(tiny_model_f32):
    """The models-layer dispatcher: 'kernel' and 'ref' impls produce
    identical greedy tokens through the single-tier paged engine."""
    model, params = tiny_model_f32
    prompts = _prompts(5, seed=8)
    tokens = {}
    for impl in ("ref", "kernel"):
        attn.set_paged_attention_impl(impl)
        try:
            eng = ServingEngine(model, params, max_batch=4, max_len=64,
                                prefill_chunk=8)
            reqs = [Request(i, p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run()
            tokens[impl] = {r.request_id: r.generated for r in reqs}
        finally:
            attn.set_paged_attention_impl(None)
    assert tokens["ref"] == tokens["kernel"]
