"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three lists of ``(name, start_ns, duration_ns)``: the device's operations,
the device's program (XLA module) executions, and the harness's own spans
on the host (``bench:<label>``, written with ``TraceAnnotation``).  Device
and host events share the profiler's clock.  Everything after ``load`` is
plain arithmetic on those lists, so it is tested on a small recorded trace
(``bench/fixtures``) without a chip.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)

SPAN_PREFIX = "bench:"


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(hlo: str) -> str:
    """An operation's own name from its HLO text (``%paged_attention.22 =
    bf16[...] custom-call(...)`` -> ``paged_attention.22``): the text also
    names the operations it reads, so only its head identifies it."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _is_device_plane(name: str) -> bool:
    """An accelerator's plane (``/device:TPU:0``), not the host's or the
    runtime's own (``/device:CUSTOM:...``)."""
    return re.fullmatch(r"/device:(TPU|GPU):\d+", name) is not None


def load(path: str, device: int = 0) -> Trace:
    """Events of device ``device``'s plane and the harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    dev = sorted(p.name for p in pd.planes if _is_device_plane(p.name))
    for plane in pd.planes:
        if dev and plane.name == dev[device]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops.extend((op_name(e.name), int(e.start_ns),
                                   int(e.duration_ns)) for e in line.events)
                elif line.name == "XLA Modules":
                    tr.modules.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                      for e in line.events)
        elif not _is_device_plane(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name[len(SPAN_PREFIX):],
                                         int(e.start_ns), int(e.duration_ns)))
    for lst in (tr.ops, tr.modules, tr.spans):
        lst.sort(key=lambda e: e[1])
    return tr


# -- arithmetic ---------------------------------------------------------------


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """``[start, end)`` of each event, clipped to ``[lo, hi)``; empty ones
    dropped."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` in which some operation ran."""
    return sum(b - a for a, b in union(clip(tr.ops, lo, hi)))


def idle_gaps(tr: Trace, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi)`` in which no operation ran."""
    gaps, t = [], lo
    for a, b in union(clip(tr.ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(tr: Trace, t: int) -> str:
    """Label of the innermost harness span covering ``t`` (``other`` where
    none does)."""
    best: Optional[Event] = None
    for ev in tr.spans:
        name, s, d = ev
        if s > t:
            break
        if s <= t < s + d and name != "window" and (best is None or d < best[2]):
            best = ev
    return best[0] if best else "other"


def window(tr: Trace) -> Tuple[int, int]:
    """The traced window: the harness's ``bench:window`` span, else the
    extent of the device's operations."""
    for name, s, d in tr.spans:
        if name == "window":
            return s, s + d
    if not tr.ops:
        raise ValueError("the trace holds no device operation")
    return tr.ops[0][1], max(s + d for _, s, d in tr.ops)


def modules_matching(tr: Trace, prefixes: Iterable[str], lo: int, hi: int
                     ) -> List[Event]:
    """Program executions whose name starts with one of ``prefixes`` and
    that start inside ``[lo, hi)``."""
    prefixes = tuple(prefixes)
    return [e for e in tr.modules if e[0].startswith(prefixes) and lo <= e[1] < hi]


def ops_inside(tr: Trace, prefix: str, within: List[Event]) -> List[Event]:
    """Operations whose name starts with ``prefix`` and that start inside
    one of the executions ``within``."""
    spans = sorted((s, s + d) for _, s, d in within)
    out, i = [], 0
    for ev in tr.ops:
        if not ev[0].startswith(prefix):
            continue
        while i < len(spans) and spans[i][1] <= ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1] < spans[i][1]:
            out.append(ev)
    return out


def self_times(events: List[Event]) -> List[Tuple[str, int, int]]:
    """``(name, start, self ns)`` of each event: its duration less that of
    the events nested directly inside it (a ``while`` holds its body's
    operations)."""
    out: List[List] = []
    stack: List[int] = []  # indices into out of the open enclosing events
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= min(d, out[stack[-1]][3] - s)
        out.append([name, s, d, s + d])
        stack.append(len(out) - 1)
    return [(n, s, max(sd, 0)) for n, s, sd, _ in out]


def top_ops(tr: Trace, lo: int, hi: int, n: int = 10) -> List[list]:
    """The ``n`` operations with the most device self time in the window,
    as ``[name, seconds]``."""
    tot: Dict[str, int] = {}
    for name, s, d in self_times([e for e in tr.ops if lo <= e[1] < hi]):
        tot[name] = tot.get(name, 0) + d
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(tr: Trace, lo: int, hi: int, n: int = 10) -> List[list]:
    """The ``n`` longest idle stretches, each as ``[label, seconds]`` with
    the harness span the host was in at its middle."""
    gaps = sorted(idle_gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[span_at(tr, (a + b) // 2), (b - a) / 1e9] for a, b in gaps]
