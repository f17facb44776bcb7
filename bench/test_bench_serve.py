"""The serve-and-compare path of a run at a small size on the CPU, with the
Pallas kernels interpreted: a sound run is correct, the control fails the
check's limits, and a run with the timed path broken underneath comes out
not correct, once for each fault a served cell can have (a token altered
where it is produced; a decode step that leaves the cache as it was).

The limits here are for this size (the chip's cells carry their own,
calibrated at their own sizes); they sit between this size's readings of
sound runs and of the control."""

import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import check, harness, manifest  # noqa: E402

SEED = 2**33 + 3
SMALL_LIMITS = {"gap_mean": 0.03, "mismatch_share": 0.09}


def _small():
    conf = json.loads((HERE / "configs" / "switch-base-8.json").read_text())
    conf.update(num_layers=6, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                vocab_size=512, d_ff=256)
    conf["moe"] = dict(conf["moe"], d_ff_expert=128)
    dep = json.loads((HERE / "cells" / "sb8-chat-poisson.json").read_text())
    dep.update(check_tokens=200, limits=SMALL_LIMITS)
    dep["engine_args"] = dict(dep["engine_args"], force_split=1, max_batch=4, max_len=160)
    mix = json.loads((HERE / "traffic" / "chat-poisson.json").read_text())
    mix["prompt_len"] = dict(mix["prompt_len"], median=40, max=120, min=8)
    mix["output_len"] = dict(mix["output_len"], median=8, max=24, min=2)
    mix["arrivals"] = dict(mix["arrivals"], rate_rps=3.0)
    return {"config": conf, "deployment": dep, "traffic": mix}


@pytest.fixture(scope="module", autouse=True)
def kernels_interpreted():
    from repro.models import attention

    attention.set_paged_attention_impl("kernel")
    yield
    attention.set_paged_attention_impl(None)


def _run(hooks=None, control=False):
    return harness.run_cell("sb8-chat-poisson", SEED, 4.0, False, root=ROOT,
                            t_start=time.perf_counter(), require_tpu=False,
                            override=_small(), log=lambda *a: None, hooks=hooks,
                            control=control)


def test_sound_run_is_correct_and_the_control_is_not():
    out = _run(control=True)
    assert out["failed"] == 0 and out["attempted"] == 12
    assert out["correct"], out["compared"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == set(SMALL_LIMITS)
    reported = [m["name"] for m in manifest.metrics_for(
        manifest.load(ROOT), "sb8-chat-poisson", "end_to_end")]
    assert set(out["metrics"]) == set(reported)
    for name in reported:
        assert out["metrics"][name]["value"] > 0
    cal = out["calibration"]
    assert cal["n_tokens"] >= 100
    ctl = {k: {"value": cal["control"][k], "limit": v} for k, v in SMALL_LIMITS.items()}
    assert not check.passed(ctl), ctl


def _alter_tokens(eng):
    fn = eng._cloud_step
    V = eng.cfg.vocab_size

    def altered(*a):
        ids, pages = fn(*a)
        return (ids + 1) % V, pages

    eng._cloud_step = altered


def _keep_cache(eng):
    end, cloud = eng._end_step, eng._cloud_step

    def end_keep(params, tokens, pages, *a):
        z, _, *rest = end(params, tokens, pages, *a)
        return (z, pages, *rest)

    def cloud_keep(params, z, pages, *a):
        ids, _ = cloud(params, z, pages, *a)
        return ids, pages

    eng._end_step, eng._cloud_step = end_keep, cloud_keep


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_cache],
                         ids=["token_altered", "cache_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    out = _run(hooks={"engine": fault})
    assert not out["correct"], out["compared"]
