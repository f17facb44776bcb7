"""Cost functions against counts made by hand."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def _load(name):
    spec = importlib.util.spec_from_file_location(name, HERE / "costs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PA = _load("paged_attention")
MF = _load("model_flops")
SB8 = json.loads((HERE / "configs" / "switch-base-8.json").read_text())
TINY = {"num_layers": 2, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4, "d_model": 8,
        "d_ff": 16, "vocab_size": 10, "ffn_gated": False,
        "layer_pattern": [{"kind": "attn", "moe": False}, {"kind": "attn", "moe": True}],
        "moe": {"num_experts": 4, "top_k": 1, "d_ff_expert": 16, "num_groups": 2}}


def test_decode_token_by_hand():
    # 2 layers; per layer K and V: 5 keys x 1 head x 4 dims x 2 bytes each;
    # q read and out written: 2 heads x 4 dims x 2 bytes each
    f, b = PA.decode_token(TINY, 5)
    assert f == 2 * 4 * 2 * 4 * 5
    assert b == 2 * (2 * 5 * 1 * 4 * 2 + 2 * 2 * 4 * 2)


def test_prefill_chunk_by_hand():
    # queries at 3, 4: 4 + 5 keys; keys read once: 5
    f, b = PA.prefill_chunk(TINY, 3, 2)
    assert f == 2 * 4 * 2 * 4 * 9
    assert b == 2 * (2 * 5 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2)


def test_switch_base_8_decode_bytes():
    # one token at 1024 keys: 12 layers x 2 (K, V) x 1024 x 12 heads x 64 x 2 B
    _, b = PA.decode_token(SB8, 1024)
    assert b == 12 * (2 * 1024 * 12 * 64 * 2 + 2 * 12 * 64 * 2)


def test_active_params_by_hand():
    # attention: q 8x2x4, k and v 8x1x4 each, o 2x4x8 -> 64+32+32+64 = 192 per layer
    # dense FFN 8x16 + 16x8 = 256; MoE: one expert 256 + router 8x4 + 8x2 = 304
    # head 8x10
    assert MF.active_params(TINY) == 192 * 2 + 256 + 304 + 80
    assert MF.active_params(TINY, with_head=False) == 192 * 2 + 256 + 304


def test_token_and_prompt_flops():
    p = MF.active_params(TINY)
    assert MF.token_flops(TINY, 3) == 2 * p + 2 * 4 * 2 * 4 * 3
    # prompt of 3: every position's body and attention, logits only at the end
    body = 2 * MF.active_params(TINY, with_head=False)
    assert MF.prompt_flops(TINY, 3) == pytest.approx(
        3 * body + 2 * 4 * 2 * 4 * (1 + 2 + 3) + 2 * 8 * 10)


def test_switch_base_8_active_params():
    # 12 attention layers of 4 x 768^2, 6 dense FFNs and 6 experts of
    # 2 x 768 x 3072, 6 routers of 768 x (8 + 4), head 768 x 32128
    want = 12 * 4 * 768**2 + 12 * 2 * 768 * 3072 + 6 * 768 * 12 + 768 * 32128
    assert MF.active_params(SB8) == want
