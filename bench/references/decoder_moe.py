"""Plain float32 reference of the decoder-only group-gated MoE stack that
``bench/configs/switch-base-*.json`` describe, and the weights it shares with
the system under test.

It imports nothing of the system under test.  Each layer is written out from
its equations: RMS norm with a zero-centred scale (``x / rms(x) * (1 + w)``),
rotary positions on the two halves of each head, causal softmax attention,
a two-matrix tanh-GELU MLP, and the two-stage group gate of
EC2MoE (eq. 5-7: a K-way softmax over groups times a softmax within each
group; experts that the end tier does not hold are masked out of its
layers, and a group with none left gets no probability).  Every expert runs
on every token and the top-k are combined with renormalized weights; no
cache, no paging, no kernels, no dispatch.

The parameter tree is the layout the system's ``init`` returns (the shapes
are read from it with ``jax.eval_shape``); the values are made here, from
the seed, in one jitted call on the device.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Standard deviation of the scale and bias leaves, which the system
# initializes at zero.  Nonzero values make the reference check that each is
# applied where it belongs.
SMALL_STD = 0.1


def _leaf_std(path: Sequence[str], shape, cfg: Dict) -> float:
    name = path[-1]
    parent = path[-2] if len(path) > 1 else ""
    d = cfg["d_model"]
    if name in ("norm1", "norm2", "final_norm", "b_local", "b_global",
                "q_norm", "k_norm"):
        return SMALL_STD
    if name == "embed":
        return 1.0
    if name in ("wq", "wk", "wv", "wi", "w_local", "w_global", "lm_head"):
        return 1.0 / math.sqrt(d)
    if name == "wo" and parent == "attn":
        return 1.0 / math.sqrt(cfg["num_heads"] * cfg["head_dim"])
    if name == "wo":
        return 1.0 / math.sqrt(shape[-2])
    raise KeyError(f"no initializer for parameter {'/'.join(path)}")


def _path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def make_params(shapes, cfg: Dict, key: jax.Array):
    """Weights for the tree ``shapes`` (ShapeDtypeStructs), each leaf
    normal with the standard deviation ``_leaf_std`` gives, in the leaf's
    own dtype, made on the device in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = [_leaf_std(_path_names(p), s.shape, cfg) for p, s in leaves]

    def build(k):
        keys = jax.random.split(k, len(leaves))
        out = [
            (std * jax.random.normal(kk, s.shape, jnp.float32)).astype(s.dtype)
            for kk, (_, s), std in zip(keys, leaves, stds)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key)


# -- the forward pass ---------------------------------------------------------


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope(x, pos, theta):
    """x [S, n, hd]; rotate the two halves by ``pos * theta**(-i/half)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq  # [S, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(p, h, cfg, rnd):
    S = h.shape[0]
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    q = rnd(jnp.einsum("sd,dhk->shk", h, p["wq"]))
    k = rnd(jnp.einsum("sd,dhk->shk", h, p["wk"]))
    v = rnd(jnp.einsum("sd,dhk->shk", h, p["wv"]))
    q, k = rnd(_rope(q, pos, cfg["rope_theta"])), rnd(_rope(k, pos, cfg["rope_theta"]))
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = rnd(jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v))
    return jnp.einsum("qhk,hkd->qd", o, p["wo"])


def _mlp(w, h, rnd):
    return rnd(_gelu_tanh(h @ w["wi"])) @ w["wo"]


def _masked_softmax(x, keep):
    """Softmax over the entries ``keep`` allows; all zeros where it allows
    none."""
    m = jnp.max(jnp.where(keep, x, -jnp.inf), -1, keepdims=True)
    e = jnp.where(keep, jnp.exp(x - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    return e / jnp.maximum(e.sum(-1, keepdims=True), jnp.finfo(jnp.float32).tiny)


def _moe(p, h, cfg, allowed, rnd):
    """Group gate (eq. 5-7) over the experts ``allowed`` ([E] bool, or
    None for all), top-k with renormalized weights, every expert run on
    every token."""
    m = cfg["moe"]
    E, K, k = m["num_experts"], m["num_groups"], m["top_k"]
    g = p["gate"]
    local = jnp.einsum("sd,kdm->skm", h, g["w_local"]) + g["b_local"]
    glob = h @ g["w_global"] + g["b_global"]
    em = (jnp.ones((K, E // K), bool) if allowed is None
          else allowed.reshape(K, E // K))
    probs = (_masked_softmax(glob, em.any(-1)[None])[:, :, None]
             * _masked_softmax(local, em[None])).reshape(h.shape[0], E)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)  # [S, E]

    def one(y, e):
        w = {n: p[n][e] for n in ("wi", "wo")}
        return y + weight[:, e, None] * _mlp(w, h, rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return y


def forward(params: Dict, cfg: Dict, tokens: jax.Array, split: int,
            end_allowed: Optional[jax.Array],
            rnd: Callable = lambda x: x) -> jax.Array:
    """Logits ``[S, vocab]`` of the whole sequence ``tokens [S]``.  Blocks
    ``< split`` are the end tier, whose MoE layers route over
    ``end_allowed`` ([E] bool); the cloud tier's route over all experts.
    ``rnd`` rounds every activation the served path holds in its
    configured dtype: the residual stream after each layer and every
    matmul input (identity for the float32 reference; a cast through a
    lower precision for the control).  Accumulation, norms, softmax, the
    router's probabilities and the logits stay float32."""
    if cfg["act"] != "gelu" or cfg.get("ffn_gated") or cfg.get("tie_embeddings"):
        raise NotImplementedError("the reference writes out an untied, ungated GELU stack")
    eps = cfg["norm_eps"]
    pattern = cfg["layer_pattern"]
    R = cfg["num_layers"] // len(pattern)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    x = rnd(f32(params["embed"])[tokens])
    for b in range(R):
        for i, spec in enumerate(pattern):
            p = jax.tree.map(lambda leaf: f32(leaf[b]), params["blocks"][f"pos{i}"])
            x = rnd(x + _attention(p["attn"], rnd(_rms_norm(x, p["norm1"], eps)), cfg, rnd))
            h = rnd(_rms_norm(x, p["norm2"], eps))
            if spec.get("moe"):
                x = rnd(x + _moe(p["moe"], h, cfg, end_allowed if b < split else None, rnd))
            else:
                x = rnd(x + _mlp(p["ffn"], h, rnd))
    x = rnd(_rms_norm(x, f32(params["final_norm"]), eps))
    return (x @ f32(params["lm_head"]))[:, : cfg["vocab_size"]]


def token_gaps(params: Dict, cfg: Dict, tokens: jax.Array, targets: jax.Array,
               split: int, end_allowed, rnd: Optional[Callable] = None):
    """Per position of ``tokens [L]``: the gap by which ``targets``' logit
    lies below the best logit, and the gap of the token a second forward
    in ``rnd``'s precision puts first, both in units of the position's
    logit standard deviation, by the float32 logits.  Runs under
    ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        ref = forward(params, cfg, tokens, split, end_allowed)
        best = ref.max(-1)
        sd = ref.std(-1)
        gap = (best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]) / sd
        if rnd is None:
            return gap, None
        low = forward(params, cfg, tokens, split, end_allowed, rnd)
        pick = jnp.argmax(low, -1)
        low_gap = (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / sd
        return gap, low_gap


def control_round(x):
    """The control's precision: float8 e4m3, the step below the
    configuration's bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def allowed_mask(n_experts: int, end_experts: Sequence[int]) -> np.ndarray:
    m = np.zeros((n_experts,), bool)
    m[list(end_experts)] = True
    return m
