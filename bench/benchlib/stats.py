"""Arithmetic over one window's records, shared by the metric readers.

All latencies are over *all* the window's requests: a request that never
delivered a token is counted at the moment the drain gave up on it, so a
failure can only raise a tail."""

from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile with linear interpolation between order
    statistics (numpy's default); NaN for no values."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else float("nan")


def ttfts(win) -> List[float]:
    """First-token time minus due time, per window request."""
    return [(r.token_times[0] if r.token_times else win.drained_at) - r.due
            for r in win.recs]


def itls(win) -> List[float]:
    """Every gap between consecutive output tokens of every window
    request."""
    out: List[float] = []
    for r in win.recs:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def served(win) -> List:
    """Every request the engine served while the window was open: the
    window's own and those sent in a closed loop's pre-roll."""
    return list(win.pre) + list(win.recs)


def tokens_between(win, lo: float, hi: float) -> int:
    """Output tokens of any request delivered in ``[lo, hi)``."""
    return sum(1 for r in served(win) for t in r.token_times if lo <= t < hi)


def lags(win) -> List[float]:
    return [r.lag_s for r in win.recs]


def failed(win) -> int:
    return sum(1 for r in win.recs if not r.done)


def step_ticks(win, lo: float = -np.inf, hi: float = np.inf) -> List:
    return [t for t in win.ticks if t.label == "step" and lo <= t.start < hi]


def decode_tokens(win, lo: float, hi: float) -> Iterable[tuple]:
    """``(prompt_len, index)`` of every output token after a request's
    first that was delivered in ``[lo, hi)``: the decode step that made
    token ``index`` attended to ``prompt_len + index`` keys."""
    for r in win.recs:
        n = len(r.req.prompt)
        for i, t in enumerate(r.token_times):
            if i and lo <= t < hi:
                yield n, i
