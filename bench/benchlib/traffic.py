"""The one traffic generator: reads a mix's parameters (``bench/traffic/
<name>.json``) and turns them, with a seed, into requests.

Two loops:

- ``open``: requests are due on a schedule of arrivals, sent whether or not
  earlier ones finished.  ``open_schedule`` returns every request due inside
  the window with its due time.
- ``closed``: ``clients`` callers each send their next request the moment
  the previous one finishes.  ``closed_pool`` returns the sequence of
  requests the clients draw from, in order.

A mix names its arrival process and its length distributions; each is a
module found by that name, ``bench/generator/arrivals/<process>.py``
(``offsets(spec, q, seconds)``) and ``bench/generator/lengths/<dist>.py``
(``ppf(spec, q)``), so a mix with a new process or distribution adds a file
and edits none.

Sizes are drawn *stratified*: a mix of ``n`` requests takes its lengths from
the ``n`` quantiles ``(i + 0.5) / n`` of the stated distribution, and a seed
only permutes them (and, in open loop, the gaps between arrivals, drawn the
same way), so every seed serves the same multiset of lengths.  A mix that
states a ``schedule_seed`` takes the order from it, so every run replays one
schedule; the run's seed then draws only the token ids, uniformly over the
vocabulary.
"""

from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from functools import lru_cache
from pathlib import Path
from typing import Dict, List

import numpy as np

GENERATOR = Path(__file__).resolve().parents[1] / "generator"


@dataclass(frozen=True)
class Req:
    """One generated request: the harness hands ``prompt`` and
    ``max_new`` to the engine; ``due_s`` is the offset from the window's
    start at which it is due (open loop; ``None`` in closed loop)."""

    index: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due_s: float | None = None


@lru_cache(maxsize=None)
def part(kind: str, name: str):
    """The generator's module ``bench/generator/<kind>/<name>.py``."""
    path = GENERATOR / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_gen_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per purpose, from any non-negative seed."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) % (1 << 63), *tag])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified draws of the length distribution ``spec["dist"]``,
    rounded, clipped to ``[spec["min"], spec["max"]]`` and permuted by
    ``rng``."""
    x = part("lengths", spec["dist"]).ppf(spec, _quantiles(n))
    out = np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)
    return out[rng.permutation(n)]


def arrival_offsets(spec: Dict, n: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due offsets of ``n`` arrivals inside ``[0, seconds)`` from the
    process ``spec["process"]``, its quantiles permuted by ``rng``."""
    return part("arrivals", spec["process"]).offsets(
        spec, _quantiles(n)[rng.permutation(n)], seconds)


def _prompts(lens: np.ndarray, vocab: int, rng) -> List[np.ndarray]:
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def n_open(mix: Dict, seconds: float) -> int:
    """Requests due inside an open-loop window of ``seconds``."""
    return max(1, int(round(float(mix["arrivals"]["rate_rps"]) * seconds)))


def _order_seed(mix: Dict, seed: int) -> int:
    return int(mix.get("schedule_seed", seed))


def open_schedule(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """Every request due inside the window, sorted by due time."""
    if mix["loop"] != "open":
        raise ValueError("open_schedule needs an open-loop mix")
    n = n_open(mix, seconds)
    order = _order_seed(mix, seed)
    due = np.sort(arrival_offsets(mix["arrivals"], n, seconds,
                                  _rng(order, "arrivals")))
    plen = lengths(mix["prompt_len"], n, _rng(order, "prompt_len"))
    olen = lengths(mix["output_len"], n, _rng(order, "output_len"))
    prompts = _prompts(plen, vocab, _rng(seed, "tokens"))
    return [Req(i, prompts[i], int(olen[i]), float(due[i])) for i in range(n)]


def closed_pool(mix: Dict, seed: int, n: int, vocab: int) -> List[Req]:
    """The first ``n`` requests of a closed loop, in the order the clients
    take them (``pool_size`` in the mix sets the stratification block: each
    block of that many requests holds the same multiset of lengths)."""
    if mix["loop"] != "closed":
        raise ValueError("closed_pool needs a closed-loop mix")
    block = int(mix.get("pool_size", 256))
    out: List[Req] = []
    rng_t = _rng(seed, "tokens")
    order = _order_seed(mix, seed)
    b = 0
    while len(out) < n:
        plen = lengths(mix["prompt_len"], block, _rng(order, f"prompt_len{b}"))
        olen = lengths(mix["output_len"], block, _rng(order, f"output_len{b}"))
        for p, o in zip(_prompts(plen, vocab, rng_t), olen):
            out.append(Req(len(out), p, int(o)))
        b += 1
    return out[:n]


def warmup_requests(seed: int, vocab: int, n: int, prompt_len: int,
                    max_new: int) -> List[Req]:
    """Requests for the set-up's warm-up pass (own stream of the seed)."""
    rng = _rng(seed, "warmup")
    return [Req(-1 - i, rng.integers(0, vocab, prompt_len).astype(np.int32),
                max_new) for i in range(n)]
