"""``BENCHMARK.json`` against the contract checks a look at the files can
make, and each check against a manifest broken on purpose."""

import copy
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

from benchlib import manifest  # noqa: E402

BENCH = manifest.load(ROOT)


def test_benchmark_json_keeps_the_contract():
    assert manifest.validate(BENCH, ROOT) == []
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_cell_has_its_files_and_limits():
    for w in BENCH["workloads"]:
        dep = manifest.deployment(w["name"], ROOT)
        assert dep["limits"], w["name"]
        conf = manifest.config(BENCH, w["config"], ROOT)
        assert (HERE / "references" / f"{conf['reference']}.py").is_file()
        assert max(dep["end_experts"]) < conf["moe"]["num_experts"]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_builds_its_engine_from_its_file_alone(name):
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import system

    dep = manifest.deployment(name, ROOT)
    kw = system.engine_kwargs(dep)
    assert set(kw) == set(dep["engine_args"])
    assert kw["end_profile"].name == dep["engine_args"]["end_profile"]
    bad = dict(dep, engine_args=dict(dep["engine_args"], no_such_option=1))
    with pytest.raises(ValueError, match="no keyword 'no_such_option'"):
        system.engine_kwargs(bad)
    with pytest.raises(ValueError, match="no engine"):
        system.engine_class("NoSuchEngine")


def _broken(edit):
    b = copy.deepcopy(BENCH)
    edit(b)
    return manifest.validate(b, ROOT)


@pytest.mark.parametrize("edit,needle", [
    (lambda b: b["end_to_end"][0].update(name="ttft p95"), "bad name"),
    (lambda b: b["end_to_end"][1].update(unit="tokens per s"), "bad unit"),
    (lambda b: b["per_layer"][0].update(unit="μs"), "bad unit"),
    (lambda b: b["per_layer"][0].update(workloads=["no-such-cell"]), "unknown cell"),
    (lambda b: b["per_layer"][1].update(moves="no_such_metric"), "moves unknown"),
    (lambda b: b["per_layer"][2].update(name="not_a_reader"), "has no reader"),
    (lambda b: b["per_layer"][4].update(unit="ratio"), "wants the unit %"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["end_to_end"][0].update(source="program_counter"), "bad source"),
    (lambda b: b["workloads"][1].update(chips=2), "chips"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="dup")), "appears twice"),
    (lambda b: b["end_to_end"].pop(3), "no setup_s"),
    (lambda b: b["workloads"][0].update(traffic="decode-closed", config="switch-base-32"),
     "appears twice"),
])
def test_each_breach_is_caught(edit, needle):
    errs = _broken(edit)
    assert any(needle in e for e in errs), errs


def test_a_metric_moving_one_that_a_listed_cell_lacks_is_caught():
    def edit(b):
        b["end_to_end"][0]["workloads"] = ["sb32-decode-closed"]
        b["per_layer"][0]["workloads"] = ["sb8-chat-poisson"]  # moves ttft_p95_s
    errs = _broken(edit)
    assert any("does not report ttft_p95_s" in e for e in errs), errs


def test_cells_report_what_their_metrics_list():
    per = manifest.metrics_for(BENCH, "sb32-decode-closed", "per_layer")
    assert "gen_lag_ms_p95" not in {m["name"] for m in per}
    per8 = manifest.metrics_for(BENCH, "sb8-chat-poisson", "per_layer")
    assert "gen_lag_ms_p95" in {m["name"] for m in per8}


def test_run_refuses_a_directory_without_the_system(tmp_path):
    (tmp_path / "bench").mkdir()
    with pytest.raises(manifest.ManifestError):
        manifest.require_system(tmp_path)
    manifest.require_system(ROOT)


def test_prng_key_takes_seeds_wider_than_32_bits():
    import jax

    a = jax.random.key_data(manifest.prng_key(jax, 2**40 + 1))
    b = jax.random.key_data(manifest.prng_key(jax, 1))
    assert a.shape == (2,) and not (a == b).all()
    assert (jax.random.key_data(manifest.prng_key(jax, 2**40 + 1)) == a).all()


def test_peaks_table_has_its_source_and_the_v5e():
    peaks = json.loads((HERE / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        "sb8-chat-poisson", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class _Jax:
    def __init__(self, devs):
        self._devs = devs

    def devices(self):
        return self._devs


def test_device_checks():
    from benchlib import harness

    peaks = json.loads((HERE / "peaks.json").read_text())
    ok = harness.device_info(_Jax([_Dev("tpu", "TPU v5 lite")]), 1, peaks, True)
    assert ok == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(harness.NoDevice):
        harness.device_info(_Jax([_Dev("cpu", "cpu")]), 1, peaks, True)
    with pytest.raises(harness.NoDevice):
        harness.device_info(_Jax([_Dev("tpu", "TPU v5 lite")]), 4, peaks, True)
    with pytest.raises(KeyError):
        harness.device_info(_Jax([_Dev("tpu", "TPU v9 imaginary")]), 1, peaks, True)
