"""The engine's own request stamps (``submit_time``, ``admit_time``,
``prefill_done_time``), read per window request for the per-layer metrics
that split the time to first token.

Per-layer metrics are read from a ``--trace 1`` run, and there the engine
stands still after the window's close while the profiler writes its trace
(several seconds at the cells' sizes).  A request whose prefill straddles
the close would count that stall as prefill; ``durations`` leaves it out.
"""

from __future__ import annotations

from typing import List, Tuple


def profiler_stall(run) -> Tuple[float, float]:
    """The stretch (harness clock) from the trace's stop to the next tick
    of the engine; empty in a run that traced nothing."""
    t_stop = run.trace_window_s[1]
    if not t_stop:
        return 0.0, 0.0
    nxt = min((t.start for t in run.window.ticks if t.start >= t_stop), default=t_stop)
    return t_stop, nxt


def durations(run, start: str, end: str) -> List[float]:
    """``end - start`` (two stamps' names) of every window request that
    has both, in seconds, less the part of the profiler's stall between
    them.  A request's stamps move onto the harness clock by the gap
    between its send and its submit stamp."""
    s0, s1 = profiler_stall(run)
    out = []
    for r in run.window.recs:
        h = r.handle
        a, b = getattr(h, start, None), getattr(h, end, None)
        if a is None or b is None:
            continue
        off = r.sent - h.submit_time
        a, b = a + off, b + off
        out.append((b - a) - max(0.0, min(b, s1) - max(a, s0)))
    return out
