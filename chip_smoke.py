#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip at switch-base's widths.

    python chip_smoke.py

Builds ``switch-base`` at its published widths (12 layers, d_model 768, 12
heads, d_ff 3072, 8 experts top-1 in 4 groups, vocab 32128) with random
weights from a seed, then in one process:

1. serves a few requests through ``EndCloudServingEngine`` with its defaults
   (paged KV, pooled experts, chunked prefill) at a forced middle split, so
   the end tier, the metered boundary and the cloud tier all run; every
   request must finish with its ``max_new_tokens``, both page pools must
   drain to zero, and no stage may compile after warm-up;
2. compares logits of a chunked prefill and a few decode steps, run through
   the paged cache by the engine's own stage functions, with the float32
   full-forward reference on the same tokens (tolerances below);
3. asserts that the compiled decode stage holds the paged-attention Pallas
   kernel (``tpu_custom_call``), so a fallback to the jnp path fails.

It refuses any platform but TPU (no CPU fallback, no interpret mode) and
exits non-zero if a phase fails.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Logit tolerances.  Errors are per position: the RMS over the vocabulary
# of (served - reference) logits, in units of the reference logits' standard
# deviation there.  The served path computes in the configuration's
# bfloat16 activations (f32 weights cast at use, f32 accumulation in
# attention, softmax, norms and router); the reference is float32 at
# "highest" matmul precision.  bf16 rounds to 8 mantissa bits (2^-9 = 0.2%
# relative) and every layer re-rounds the residual stream, so the typical
# position carries about 1% of relative error (measured on a v5e at full
# width: median 1.10-1.16%; on the CPU at smoke widths: 0.8-1.2%).
#
# MEDIAN_REL_RMS bounds the median position, over the whole sequence and
# over the decode steps alone, at about twice that floor.  A stream held in
# a lower precision than the configuration's bf16 fails it: on the CPU at
# smoke widths an fp8 attention output reads 2.5% and an fp8 final hidden
# state 3.3%.  So does a fault that moves most positions.
MEDIAN_REL_RMS = 0.02
# Top-1 routing is discontinuous: where a token's two best experts score
# within the bf16 error of each other, served path and reference pick
# different experts and that position's logits move by O(1) of their spread;
# the changed hidden state then reaches later positions through attention.
# Such positions are rare (4-7 in 100 on the v5e, each starting at a router
# near-tie); POS_REL_RMS, 10x the floor, marks them, and at most
# MAX_OFF_FRACTION of the positions may lie above it.  A page-table,
# ring-mask or kernel fault puts most positions near or above 1.
POS_REL_RMS = 0.1
MAX_OFF_FRACTION = 0.2
# Router near-tie for the log: a log-ratio between the last chosen and the
# first unchosen expert's probability below this, in any MoE layer.
NEAR_TIE = 0.05


def check(ok, *what):
    """Fail the run (an assert would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(*what)


def _device_line(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def reference_logits(cfg, params, tokens, split, end_masks):
    """float32 forward of one whole sequence ``tokens [S]``, written out
    plainly: no cache, no paging, no kernels, no dispatch (every expert runs
    on every token and the top-k are combined).  The end tier's MoE layers
    (blocks ``< split``) route under ``end_masks`` (pattern position ->
    ``[split, E]`` bool: the experts the served end tier may route to, its
    eq. 2-4 mask AND its resident slabs); cloud layers route over every
    expert.  Returns ``(logits [S, V], margin [S])`` where ``margin`` is, per
    position, the smallest log-ratio over all MoE layers between the last
    chosen and the first unchosen expert's router probability.  Call it under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp

    from repro.core import gating
    from repro.models import attention as attn
    from repro.models import transformer
    from repro.models.layers import apply_mlp, rms_norm

    cfg32 = cfg.replace(dtype="float32")
    eps, k = cfg.norm_eps, cfg.moe.top_k if cfg.moe else 0
    S = tokens.shape[0]
    x = transformer.embed_inputs(params, cfg32, tokens[None])  # [1, S, d]
    angles = attn.rope_angles(
        jnp.arange(S)[None], cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
    )
    margin = jnp.full((S,), jnp.inf, jnp.float32)
    for b in range(cfg.block_repeat):
        for i, spec in enumerate(cfg.layer_pattern):
            p = jax.tree.map(lambda leaf: leaf[b], params["blocks"][f"pos{i}"])
            h = rms_norm(x, p["norm1"], eps)
            q, kk, v = attn.project_qkv(p["attn"], h, cfg32, angles)
            o = attn.reference_attention(
                q, kk, v, causal=True, window=cfg.sliding_window
            )
            x = x + attn.output_proj(p["attn"], o)
            h = rms_norm(x, p["norm2"], eps)[0]  # [S, d]
            if spec.moe:
                mp = p["moe"]
                mask = end_masks[f"pos{i}"][b] if b < split else None
                probs, _, _ = gating.group_gate_probs(mp["gate"], h, cfg.moe, mask)
                top = jax.lax.top_k(probs, k + 1)[0]
                margin = jnp.minimum(
                    margin, jnp.log(top[:, k - 1]) - jnp.log(top[:, k])
                )
                idx, w = gating.select_topk(probs, k)
                y_all = jax.vmap(
                    lambda e: apply_mlp(
                        {n: mp[n][e] for n in ("wi", "wg", "wo") if n in mp},
                        h, cfg.act,
                    )
                )(jnp.arange(cfg.moe.num_experts))  # [E, S, d]
                y = sum(
                    w[:, j, None] * y_all[idx[:, j], jnp.arange(S)]
                    for j in range(k)
                )
                if "shared" in mp:
                    y = y + apply_mlp(mp["shared"], h, cfg.act)
            else:
                y = apply_mlp(p["ffn"], h, cfg.act)
            x = x + y[None]
    return transformer.lm_logits(params, cfg32, x)[0], margin


def _end_tier_masks(eng):
    """Per end-tier block, the experts the pooled end stage can route to:
    the applied eq. 2-4 target mask AND residency in the slab tables."""
    import jax.numpy as jnp

    masks = {}
    emask = jnp.asarray(eng._emask_dev, bool)
    S = eng._s_cap
    for pos, tab in eng._expert_tables.items():
        masks[pos] = jnp.logical_and(emask[None, :], tab["slot"] < S)
    return masks


def served_logits(eng, prompt, cont):
    """Logits at every position of ``prompt + cont`` computed by the engine's
    own compiled stage functions through its page pools: chunked prefill of
    ``prompt`` (one slot), then one decode step per token of ``cont``.
    Returns ``[len(prompt) + len(cont), V]``; leaves the pools as found."""
    import jax
    import jax.numpy as jnp

    from repro.models import kvcache

    check(not eng.busy(), "the logit check runs on an idle engine")
    slot, cslot = 0, eng._cslot(0)
    C, gsz = eng.prefill_chunk, eng._group_size
    need = kvcache.pages_needed(
        len(prompt) + len(cont), eng.page_size, eng.pages_per_slot
    )
    eng.end_pool.reserve(slot, need)
    eng.cloud_pool.reserve(cslot, need)
    eargs = (eng._emask_dev, eng._eres()) if eng._expert_pooled else ()
    cloud_prefill = jax.jit(eng.cloud_logit_fns["cloud_prefill_chunk"])
    cloud_decode = jax.jit(eng.cloud_logit_fns["cloud_step"])
    end_pages, cloud_pages = eng._end_pages, eng._cloud_pages
    rows = []
    try:
        for p0 in range(0, len(prompt), C):
            v = min(C, len(prompt) - p0)
            eng.end_pool.map_range(slot, p0, p0 + v)
            eng.cloud_pool.map_range(cslot, p0, p0 + v)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :v] = prompt[p0 : p0 + v]
            start = jnp.asarray([p0], jnp.int32)
            valid = jnp.asarray([v], jnp.int32)
            z, end_pages, *_ = eng._end_prefill_chunk(
                eng.end_params, jnp.asarray(chunk), end_pages,
                eng.end_pool.device_rows([slot]), start, valid, *eargs,
            )
            logits, cloud_pages = cloud_prefill(
                eng.cloud_params, z, cloud_pages,
                eng.cloud_pool.device_rows([cslot]), start, valid,
            )
            rows.append(logits[0, :v])
        active = np.zeros((gsz,), bool)
        active[slot] = True
        for t, tok in enumerate(cont):
            pos = len(prompt) + t
            eng.end_pool.append(slot, pos)
            eng.cloud_pool.append(cslot, pos)
            tokens = np.zeros((gsz, 1), np.int32)
            tokens[slot, 0] = tok
            lengths = np.zeros((gsz,), np.int32)
            lengths[slot] = pos
            lengths = jnp.asarray(lengths)
            z, end_pages, *_ = eng._end_step(
                eng.end_params, jnp.asarray(tokens), end_pages,
                eng.end_pool.device_rows(range(gsz), active=active), lengths,
                *eargs,
            )
            logits, cloud_pages = cloud_decode(
                eng.cloud_params, z, cloud_pages,
                eng.cloud_pool.device_rows(
                    [eng._cslot(s) for s in range(gsz)], active=active
                ),
                lengths,
            )
            rows.append(logits[slot][None])
        return np.asarray(jnp.concatenate(rows).astype(jnp.float32))
    finally:
        eng.end_pool.free(slot)
        eng.cloud_pool.free(cslot)


def logit_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per position: RMS of ``got - want`` over the vocabulary, in units of
    the reference logits' standard deviation there."""
    err = np.sqrt(np.mean((got - want) ** 2, axis=-1))
    return err / np.std(want, axis=-1)


def serve_and_compare(cfg, *, seed: int = 0, prompt_lens=(64, 96, 128, 192, 256, 160),
                      new_tokens: int = 32, max_batch: int = 8,
                      check_prompt_len: int = 100, check_decode: int = 8,
                      log=print) -> dict:
    """Serve ``len(prompt_lens)`` requests of ``cfg`` through the streaming
    end-cloud engine at a middle split, then compare logits with the float32
    reference.  Raises ``AssertionError`` on any failed check; returns the
    run's numbers."""
    import jax
    import jax.numpy as jnp

    from repro.core.hardware import PROFILES
    from repro.models.model import build_model
    from repro.serving import EndCloudServingEngine, Request

    R = cfg.block_repeat
    split = R // 2
    check(0 < split < R, f"{cfg.name}: {R} blocks leave no middle split")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    max_len = max(max(prompt_lens) + new_tokens, check_prompt_len + check_decode)

    t0 = time.perf_counter()
    eng = EndCloudServingEngine(
        model, params,
        end_profile=PROFILES["xeon-4214r"], cloud_profile=PROFILES["tpu-v5e"],
        max_batch=max_batch, max_len=max_len, force_split=split,
    )
    warm_s = time.perf_counter() - t0
    check(eng.split == split, (eng.split, split))
    warm_traces = eng.stage_trace_counts()
    log(f"engine: split {split}/{R} blocks, {eng.n_groups} groups of "
        f"{eng._group_size}, page {eng.page_size}, chunk {eng.prefill_chunk}; "
        f"warm-up compile {warm_s:.1f} s, traces {warm_traces}")

    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=new_tokens)
        for i, n in enumerate(prompt_lens)
    ]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    serve_s = time.perf_counter() - t0
    check(len(done) == len(reqs), (len(done), len(reqs)))
    for r in reqs:
        check(r.done and len(r.generated) == new_tokens, (
            r.request_id, len(r.generated)
        ))
        check(all(0 <= t < cfg.vocab_size for t in r.generated), r.generated)
    check(eng.end_pool.pages_in_use == 0, eng.end_pool.pages_in_use)
    check(eng.cloud_pool.pages_in_use == 0, eng.cloud_pool.pages_in_use)
    check(eng.stage_trace_counts() == warm_traces, (
        eng.stage_trace_counts(), warm_traces
    ))
    m = eng.metrics()
    n_tok = sum(len(r.generated) for r in reqs)
    log(f"served {len(reqs)} requests, {n_tok} new tokens, "
        f"{sum(prompt_lens)} prompt tokens in {serve_s:.2f} s wall "
        f"(prefill chunks {m['prefill_chunks']}, decode steps "
        f"{m['n_stage_steps']}, boundary bytes {m['bytes_up']})")

    # -- logits through the paged cache vs the float32 reference ----------
    toks = rng.integers(0, cfg.vocab_size, check_prompt_len + check_decode)
    prompt, cont = toks[:check_prompt_len], toks[check_prompt_len:]
    t0 = time.perf_counter()
    got = served_logits(eng, prompt, cont)
    check(eng.end_pool.pages_in_use == 0 == eng.cloud_pool.pages_in_use)
    masks = _end_tier_masks(eng) if eng._expert_pooled else {}
    with jax.default_matmul_precision("highest"):
        want, margin = jax.jit(reference_logits, static_argnums=(0, 3))(
            cfg, params, jnp.asarray(toks, jnp.int32), split, masks
        )
    want, margin = np.asarray(want, np.float32), np.asarray(margin)
    check_s = time.perf_counter() - t0
    V = cfg.vocab_size
    got, want = got[:, :V], want[:, :V]
    check(got.shape == want.shape == (len(toks), V), (got.shape, want.shape))
    check(np.isfinite(got).all() and np.isfinite(want).all())
    err = logit_errors(got, want)
    med = float(np.median(err))
    med_dec = float(np.median(err[check_prompt_len:]))
    off = err > POS_REL_RMS
    log(f"logits vs float32 reference over {len(err)} positions "
        f"({check_prompt_len} prefill + {check_decode} decode): relative RMS "
        f"median {med:.4g}, decode median {med_dec:.4g} (tol "
        f"{MEDIAN_REL_RMS}); {int(off.sum())} positions above {POS_REL_RMS} "
        f"(tol {MAX_OFF_FRACTION:.0%}), {int((off & (margin < NEAR_TIE)).sum())}"
        f" of them at a router near-tie; max {err.max():.4g}; decode "
        f"{np.array2string(err[check_prompt_len:], precision=4)}; "
        f"{check_s:.1f} s")
    check(med <= MEDIAN_REL_RMS, ("median", med, MEDIAN_REL_RMS))
    check(med_dec <= MEDIAN_REL_RMS, ("decode median", med_dec, MEDIAN_REL_RMS))
    check(off.mean() <= MAX_OFF_FRACTION, (
        "positions off", np.nonzero(off)[0], margin[off], MAX_OFF_FRACTION
    ))
    return {
        "engine": eng,
        "warm_s": warm_s,
        "serve_s": serve_s,
        "tokens": n_tok,
        "err_median": med,
        "err_median_decode": med_dec,
        "err": err,
        "margin": margin,
    }


def decode_stage_hlo(eng) -> str:
    """Compiled HLO text of the engine's decode end stage at its served
    shapes (the persistent compile cache makes this a lookup)."""
    import jax.numpy as jnp

    gsz = eng._group_size
    inactive = np.zeros((gsz,), bool)
    eargs = (eng._emask_dev, eng._eres()) if eng._expert_pooled else ()
    return eng._end_step._fn.lower(
        eng.end_params, jnp.zeros((gsz, 1), jnp.int32), eng._end_pages,
        eng.end_pool.device_rows(range(gsz), active=inactive),
        jnp.zeros((gsz,), jnp.int32), *eargs,
    ).compile().as_text()


def paged_kernel_calls(hlo: str) -> int:
    """Paged-attention Pallas calls in compiled HLO text.  Other Pallas
    kernels (e.g. the TPU lowering of ``ragged_dot``) are also
    ``tpu_custom_call``s, so the kernel is told apart by its name."""
    return sum(
        1 for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and "paged_attention" in line
    )


def main() -> int:
    import jax

    dev = _device_line(jax)
    print(f"jax {jax.__version__}; devices: {dev}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']!r}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("switch-base")
    print(f"model: {cfg.name} layers {cfg.num_layers} d_model {cfg.d_model} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} d_ff {cfg.d_ff} experts "
          f"{cfg.moe.num_experts} top-{cfg.moe.top_k} groups "
          f"{cfg.moe.num_groups} vocab {cfg.vocab_size} dtype {cfg.dtype}")
    t0 = time.perf_counter()
    out = serve_and_compare(cfg)
    n_kernel = paged_kernel_calls(decode_stage_hlo(out["engine"]))
    check(n_kernel > 0, "the decode stage lost the paged-attention kernel")
    print(f"decode stage HLO: {n_kernel} paged-attention tpu_custom_call(s)")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak device memory {stats.get('peak_bytes_in_use', 'not reported')} "
          f"bytes; total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
