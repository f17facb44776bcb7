"""The traffic generator: the same seed gives the same requests, every seed
the same multiset of sizes, and each mix's stated limits hold."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from benchlib import traffic  # noqa: E402

BIG = 2**33 + 12345  # seeds wider than 32 bits are valid
MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _requests(mix, seed, seconds=30.0):
    if mix["loop"] == "open":
        return traffic.open_schedule(mix, seed, seconds, 32128)
    return traffic.closed_pool(mix, seed, 300, 32128)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a, b = _requests(mix, BIG), _requests(mix, BIG)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.due_s == y.due_s
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_sizes_in_another_order(name):
    mix = {k: v for k, v in _mix(name).items() if k != "schedule_seed"}
    a, b = _requests(mix, 1), _requests(mix, BIG)
    n = len(a) if mix["loop"] == "open" else mix["pool_size"]
    for size in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(size, a[:n])) == sorted(map(size, b[:n]))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_a_schedule_seed_replays_one_schedule_with_other_tokens(name):
    mix = _mix(name)
    assert "schedule_seed" in mix
    a, b = _requests(mix, 1), _requests(mix, BIG)
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == [
        (len(r.prompt), r.max_new, r.due_s) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_ids_inside_the_stated_limits(name):
    mix = _mix(name)
    rs = _requests(mix, 7)
    p = [len(r.prompt) for r in rs]
    o = [r.max_new for r in rs]
    assert min(p) >= mix["prompt_len"]["min"] and max(p) <= mix["prompt_len"]["max"]
    assert min(o) >= mix["output_len"]["min"] and max(o) <= mix["output_len"]["max"]
    ids = np.concatenate([r.prompt for r in rs])
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 32128


def test_open_schedule_fills_the_window_at_the_stated_rate():
    mix = {"loop": "open", "arrivals": {"process": "poisson", "rate_rps": 5.0},
           "prompt_len": {"dist": "lognormal", "median": 6, "sigma": 0.3, "min": 4, "max": 8},
           "output_len": {"dist": "lognormal", "median": 2, "sigma": 0.3, "min": 1, "max": 2}}
    rs = traffic.open_schedule(mix, 3, 20.0, 100)
    due = [r.due_s for r in rs]
    assert len(rs) == 100 and due[0] == 0.0 and due == sorted(due)
    assert due[-1] < 20.0
    assert np.mean(np.diff(due)) == pytest.approx(20.0 / 100, rel=0.05)


def test_lognormal_quantiles_hold_the_median():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024}
    x = traffic.lengths(spec, 1001, np.random.default_rng(0))
    assert np.median(x) == 256
    assert x.min() >= 32 and x.max() == 1024


def test_lognormal_stated_by_its_mean_holds_the_mean():
    spec = {"dist": "lognormal", "mean": 338.0, "sigma": 0.8, "min": 1, "max": 10**6}
    x = traffic.lengths(spec, 4001, np.random.default_rng(0))
    assert np.mean(x) == pytest.approx(338.0, rel=0.02)
    assert np.median(x) == pytest.approx(338.0 * np.exp(-0.32), abs=1)


@pytest.mark.parametrize("name", MIXES)
def test_each_mix_names_a_source_and_parts_the_generator_has(name):
    mix = _mix(name)
    assert mix["source"] and mix["why"]
    parts = [("lengths", mix["prompt_len"]["dist"]), ("lengths", mix["output_len"]["dist"])]
    if mix["loop"] == "open":
        parts.append(("arrivals", mix["arrivals"]["process"]))
    for kind, part in parts:
        assert traffic.part(kind, part) is not None


def test_an_unknown_part_is_an_error():
    with pytest.raises(ValueError, match="no arrivals module"):
        traffic.arrival_offsets({"process": "no-such"}, 4, 1.0, np.random.default_rng(0))
