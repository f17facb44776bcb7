"""Operations and bytes the paged-attention kernel's work needs, counted
from the request shapes the harness generated (not from the kernel's padded
grid): every query attends to every key at or before its position, each
key and value is read once per call, and each query and output row is read
or written once."""

from __future__ import annotations

from typing import Dict, Tuple


def _dims(cfg: Dict) -> Tuple[int, int, int, int]:
    n_attn = cfg["num_layers"]  # every layer of these stacks is attention
    return n_attn, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]


def decode_token(cfg: Dict, ctx: int, kv_bytes: int = 2, act_bytes: int = 2
                 ) -> Tuple[float, float]:
    """(flops, bytes) over all layers for one decode query that attends to
    ``ctx`` keys (itself included)."""
    L, H, KV, hd = _dims(cfg)
    flops = L * 4.0 * H * hd * ctx
    nbytes = L * (2.0 * ctx * KV * hd * kv_bytes + 2.0 * H * hd * act_bytes)
    return flops, nbytes


def prefill_chunk(cfg: Dict, start: int, n_valid: int, kv_bytes: int = 2,
                  act_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) over all layers for one chunk of ``n_valid`` queries
    at positions ``start .. start + n_valid - 1``."""
    L, H, KV, hd = _dims(cfg)
    keys = sum(start + i + 1 for i in range(n_valid))
    flops = L * 4.0 * H * hd * keys
    nbytes = L * (2.0 * (start + n_valid) * KV * hd * kv_bytes
                  + 2.0 * n_valid * H * hd * act_bytes)
    return flops, nbytes
