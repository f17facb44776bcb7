"""``BENCHMARK.json`` and the files it names: loading, lookup by name, and
the checks of the benchmark's contract that can be made without a run."""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List

import numpy as np

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The benchmark's files are missing or break its contract."""


def _read(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise ManifestError(f"missing {path}") from e


def load(root: Path) -> Dict:
    return _read(Path(root) / "BENCHMARK.json")


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: Dict, name: str, root: Path) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _read(Path(root) / c["file"])
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path) -> Dict:
    return _read(Path(root) / "bench" / "traffic" / f"{name}.json")


def deployment(workload: str, root: Path) -> Dict:
    return _read(Path(root) / "bench" / "cells" / f"{workload}.json")


def metrics_for(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports: those
    without a ``workloads`` key, and those that list it."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def require_system(root: Path):
    """The system under test must be beside the benchmark."""
    if not (Path(root) / "src" / "repro" / "serving").is_dir():
        raise ManifestError(f"no system under test at {Path(root) / 'src' / 'repro'}")


def prng_key(jax, seed: int):
    """A JAX key from any non-negative seed, wider than 32 bits too."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _mix_errors(name: str, mix: Dict, root: Path) -> List[str]:
    """A mix's loop, and a generator module for each part it names."""
    gen = Path(root) / "bench" / "generator"
    parts = [("lengths", mix["prompt_len"]["dist"]), ("lengths", mix["output_len"]["dist"])]
    if mix["loop"] == "open":
        parts.append(("arrivals", mix["arrivals"]["process"]))
    elif mix["loop"] != "closed":
        return [f"traffic {name}: unknown loop {mix['loop']!r}"]
    return [f"traffic {name}: no generator module {kind}/{part}.py"
            for kind, part in parts if not (gen / kind / f"{part}.py").is_file()]


def validate(bench: Dict, root: Path) -> List[str]:
    """Breaches of the contract that a look at the files shows."""
    errs: List[str] = []
    root = Path(root)
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            if not NAME.match(e["name"]):
                errs.append(f"bad name {e['name']!r}")
            if e["name"] in names:
                errs.append(f"name {e['name']!r} used twice")
            names.add(e["name"])
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        f = root / c["file"]
        if not f.is_file():
            errs.append(f"config file {c['file']} missing")
        elif not any(Path(c["file"]).parts[0] == Path(p).parts[0] for p in bench["paths"]):
            errs.append(f"config file {c['file']} outside paths")
        for k in c["reduced"]:
            if not NAME.match(k):
                errs.append(f"bad reduced key {k!r}")
    cells = {}
    for w in bench["workloads"]:
        cells[w["name"]] = w
        if w["config"] not in configs:
            errs.append(f"cell {w['name']} names unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']} asks for {w['chips']} chips")
        for k in ("config", "traffic"):
            if not NAME.match(w[k]):
                errs.append(f"bad {k} {w[k]!r}")
        mix_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
        if not mix_file.is_file():
            errs.append(f"traffic {w['traffic']} has no file")
        else:
            errs.extend(_mix_errors(w["traffic"], _read(mix_file), root))
        if not (root / "bench" / "cells" / f"{w['name']}.json").is_file():
            errs.append(f"cell {w['name']} has no file")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            errs.append(f"cell {w['name']}: why too long or not one line")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    if len(set(pairs)) != len(pairs):
        errs.append("a pair of configuration and traffic appears twice")
    used = {w["config"] for w in bench["workloads"]}
    for c in configs:
        if c not in used:
            errs.append(f"config {c} used by no cell")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if not UNIT.match(m["unit"]):
                errs.append(f"bad unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                errs.append(f"bad better of {m['name']}")
            ok = SOURCES_E2E if group == "end_to_end" else SOURCES
            if m["source"] not in ok:
                errs.append(f"bad source of {m['name']}")
            for w in m.get("workloads", []):
                if w not in cells:
                    errs.append(f"{m['name']} lists unknown cell {w}")
            if not (root / "bench" / "metrics" / f"{m['name']}.py").is_file():
                errs.append(f"metric {m['name']} has no reader")
            if (m["name"].endswith("_roofline") or "mfu" in m["name"]) and m["unit"] != "%":
                errs.append(f"{m['name']} is a share and wants the unit %")
    for m in bench["end_to_end"]:
        b = m.get("bound")
        if b is None or not (0.01 <= b <= 0.25):
            errs.append(f"bound of {m['name']} outside [0.01, 0.25]")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            errs.append(f"{m['name']} moves unknown metric {m['moves']}")
            continue
        moved = e2e[m["moves"]]
        for w in m.get("workloads", list(cells)):
            if w not in moved.get("workloads", list(cells)):
                errs.append(f"{m['name']} in {w}, which does not report {m['moves']}")
    for w in cells:
        kinds = [metrics_for(bench, w, k) for k in ("end_to_end", "per_layer")]
        if len(kinds[0]) < 2 or not kinds[1]:
            errs.append(f"cell {w} reports too few metrics")
    for m in bench["per_layer"]:
        if not m["layer"] or "\n" in m["layer"] or len(m["layer"]) > 200:
            errs.append(f"bad layer of {m['name']}")
    return errs
