"""Model operations per token: twice the parameters a token passes through
(the attention projections, the dense FFNs, the ``top_k`` experts and the
router of each MoE layer, and the LM head where logits are made), plus the
attention scores and weighted values over its context.  The embedding
lookup counts nothing."""

from __future__ import annotations

from typing import Dict


def active_params(cfg: Dict, with_head: bool = True) -> float:
    d, H, KV, hd = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    mats = 3 if cfg.get("ffn_gated") else 2
    pattern = cfg["layer_pattern"]
    R = cfg["num_layers"] // len(pattern)
    n = 0.0
    for spec in pattern:
        n += d * (H + 2 * KV) * hd + H * hd * d
        if spec.get("moe"):
            m = cfg["moe"]
            n += m["top_k"] * mats * d * m["d_ff_expert"] + d * m["num_experts"] + d * m["num_groups"]
        else:
            n += mats * d * cfg["d_ff"]
    n *= R
    if with_head:
        n += d * cfg["vocab_size"]
    return n


def token_flops(cfg: Dict, ctx: int, with_head: bool = True) -> float:
    """One token attending to ``ctx`` keys (itself included)."""
    attn = cfg["num_layers"] * 4.0 * cfg["num_heads"] * cfg["head_dim"] * ctx
    return 2.0 * active_params(cfg, with_head) + attn


def prompt_flops(cfg: Dict, n: int) -> float:
    """A prompt of ``n`` tokens: logits only at its last position."""
    body = 2.0 * active_params(cfg, with_head=False) * n
    attn = cfg["num_layers"] * 4.0 * cfg["num_heads"] * cfg["head_dim"] * (n * (n + 1) / 2)
    return body + attn + 2.0 * cfg["d_model"] * cfg["vocab_size"]
