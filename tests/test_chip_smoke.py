"""``chip_smoke.py`` on the CPU: its serve-and-compare phase at smoke widths,
and its refusal to run anywhere but on a TPU.

The serve-and-compare function is the one the chip run calls at
switch-base's published widths; here it runs ``smoke_config`` of the same
model at two blocks (the fewest that leave a middle split), with the
paged-attention Pallas kernel pinned and therefore interpreted.
"""

import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config, smoke_config
from repro.models import attention as attn

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)


@pytest.fixture
def kernel_attention():
    attn.set_paged_attention_impl("kernel")
    yield
    attn.set_paged_attention_impl(None)


def test_serve_and_compare_switch_base_smoke(kernel_attention):
    cfg = smoke_config(get_config("switch-base")).replace(num_layers=4)
    lines = []
    out = chip_smoke.serve_and_compare(
        cfg, prompt_lens=(12, 20, 9), new_tokens=5, max_batch=4,
        check_prompt_len=40, check_decode=4, log=lines.append,
    )
    eng = out["engine"]
    assert 0 < eng.split < cfg.block_repeat
    assert out["tokens"] == 3 * 5
    assert out["err_median"] <= chip_smoke.MEDIAN_REL_RMS
    assert out["err_median_decode"] <= chip_smoke.MEDIAN_REL_RMS
    assert out["err"].shape == (44,)
    assert eng.end_pool.pages_in_use == 0 == eng.cloud_pool.pages_in_use
    assert any("logits vs float32 reference" in line for line in lines)


def test_main_refuses_non_tpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
