"""What the program's own instrumentation adds to a profiler trace, and
the arithmetic on it.

- The engine's host spans (``engine:<phase>``, written by
  ``repro.serving.common.span``), on the profiler's clock like the
  harness's ``bench:`` spans, with their arguments (a request's id, a slot,
  a group) as ``ids``.  ``step`` holds one span per phase of a tick, and a
  phase holds a ``sync`` span wherever the host waits for the device.
- Each device operation's name stack (``benchlib/xplane.py``), where the
  model's name scopes appear: ``moe`` (with ``gate`` and ``experts``),
  ``attention``, ``kv_write``, ``mlp``, ``lm_head``, ``codec``.

``load`` reads everything ``trace.load`` reads, unchanged, and adds both.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from benchlib import trace as T
from benchlib import xplane

ENGINE_PREFIX = "engine:"
PHASES = ("drain", "harvest", "prefetch", "replan", "admit", "prefill", "resolve",
          "draft", "activate", "end_stage")
SCOPES = ("moe", "attention", "kv_write", "mlp", "lm_head", "codec")
DECODE = ("jit_end_step", "jit_cloud_step")
PREFILL = ("jit_end_prefill_chunk", "jit_cloud_prefill_chunk")

Span = Tuple[str, int, int, Dict]  # (phase, start_ns, duration_ns, ids)


@dataclass
class EngineTrace(T.Trace):
    engine: List[Span] = field(default_factory=list)
    # (operation name, start_ns) -> the operation's name stack
    stacks: Dict[Tuple[str, int], str] = field(default_factory=dict)


def load(path: str, device: int = 0) -> EngineTrace:
    from jax.profiler import ProfileData

    base = T.load(path, device)
    tr = EngineTrace(ops=base.ops, modules=base.modules, spans=base.spans)
    with open(path, "rb") as f:
        buf = f.read()
    pd = ProfileData.from_file(path)
    dev = sorted(p.name for p in pd.planes if T._is_device_plane(p.name))
    for plane in pd.planes:
        if dev and plane.name == dev[device]:
            events = [e for line in plane.lines if line.name == "XLA Ops"
                      for e in line.events]
            stacks = xplane.op_stacks(buf, plane.name)
            if len(stacks) == len(events) and all(
                    e.name == n for e, (n, _) in zip(events, stacks)):
                tr.stacks = {(T.op_name(e.name), int(e.start_ns)): s
                             for e, (_, s) in zip(events, stacks) if s}
        elif not T._is_device_plane(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ENGINE_PREFIX):
                        name = e.name[len(ENGINE_PREFIX):].split("#", 1)[0]
                        tr.engine.append((name, int(e.start_ns), int(e.duration_ns),
                                          dict(e.stats)))
    tr.engine.sort(key=lambda s: (s[1], -s[2]))
    return tr


# -- host spans -----------------------------------------------------------


def spans_named(tr, name: str, lo: int, hi: int) -> List[Span]:
    """Engine spans called ``name`` lying wholly inside ``[lo, hi)``."""
    return [s for s in getattr(tr, "engine", ()) if s[0] == name
            and lo <= s[1] and s[1] + s[2] <= hi]


def _durations_inside(spans: List[Span], steps: List[Span]) -> List[int]:
    """Per step, the summed durations of ``spans`` that lie inside it."""
    out, i = [], 0
    spans = sorted(spans, key=lambda s: s[1])
    for st in steps:
        a, b = st[1], st[1] + st[2]
        while i < len(spans) and spans[i][1] < a:
            i += 1
        j, tot = i, 0
        while j < len(spans) and spans[j][1] < b:
            if spans[j][1] + spans[j][2] <= b:
                tot += spans[j][2]
            j += 1
        out.append(tot)
    return out


def host_ms_per_tick(tr, lo: int, hi: int) -> Optional[float]:
    """Mean over the ``step`` spans inside ``[lo, hi)`` of the step's
    duration less its ``sync`` spans: the host's own time in a tick, in
    milliseconds."""
    steps = spans_named(tr, "step", lo, hi)
    if not steps:
        return None
    sync = _durations_inside(spans_named(tr, "sync", lo, hi), steps)
    return sum(s[2] - y for s, y in zip(steps, sync)) / len(steps) / 1e6


def phase_ms_per_tick(tr, lo: int, hi: int) -> Dict[str, Dict[str, float]]:
    """Per phase, its mean time per step (``ms``) and the part of it the
    host waited for the device (``sync_ms``)."""
    steps = spans_named(tr, "step", lo, hi)
    if not steps:
        return {}
    syncs = spans_named(tr, "sync", lo, hi)
    out = {}
    for ph in PHASES + ("gc",):
        spans = spans_named(tr, ph, lo, hi)
        if not spans:
            continue
        inner = _durations_inside(syncs, spans)
        out[ph] = {"ms": sum(s[2] for s in spans) / len(steps) / 1e6,
                   "sync_ms": sum(inner) / len(steps) / 1e6,
                   "n_per_tick": len(spans) / len(steps)}
    return out


def phase_share(tr, lo: int, hi: int) -> Optional[float]:
    """Share of the ``step`` spans' time that their phase spans cover, in
    percent."""
    steps = spans_named(tr, "step", lo, hi)
    if not steps:
        return None
    phases = [s for s in getattr(tr, "engine", ()) if s[0] in PHASES]
    covered = sum(_durations_inside(phases, steps))
    return 100.0 * covered / sum(s[2] for s in steps)


def label_at(tr, t: int) -> str:
    """``engine:<phase>`` of the innermost engine span covering ``t``, else
    the harness's label (``trace.span_at``)."""
    best: Optional[Span] = None
    for s in getattr(tr, "engine", ()):
        if s[1] > t:
            break
        if s[1] <= t < s[1] + s[2] and (best is None or s[2] < best[2]):
            best = s
    return ENGINE_PREFIX + best[0] if best else T.span_at(tr, t)


def top_gaps(tr, lo: int, hi: int, n: int = 10) -> List[list]:
    """``trace.top_gaps`` with each gap labelled by ``label_at``."""
    gaps = sorted(T.idle_gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label_at(tr, (a + b) // 2), (b - a) / 1e9] for a, b in gaps]


def idle_by_label(tr, lo: int, hi: int) -> Dict[str, float]:
    """Seconds of device idle time in ``[lo, hi)`` by ``label_at`` of each
    gap's middle."""
    out: Dict[str, float] = {}
    for a, b in T.idle_gaps(tr, lo, hi):
        k = label_at(tr, (a + b) // 2)
        out[k] = out.get(k, 0.0) + (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# -- device name scopes ---------------------------------------------------


def scopes_of(stack: str) -> List[str]:
    """The components of a name stack (``jit(f)/while/body/moe/experts/
    dot_general:`` -> ``[..., "moe", "experts", "dot_general"]``), the
    operation's type after the last ``:`` left out."""
    return re.sub(r":[^/]*$", "", stack).split("/") if stack else []


def scoped_self_ns(tr, prefixes: Iterable[str], lo: int, hi: int
                   ) -> Tuple[Dict[str, int], int]:
    """Device self time (ns) of the operations inside the executions of the
    programs named by ``prefixes`` that start in ``[lo, hi)``, by the first
    of ``SCOPES`` in each operation's name stack (``"none"`` where none
    is), and the number of those executions whose name starts with the
    first prefix."""
    prefixes = tuple(prefixes)
    mods = T.modules_matching(tr, prefixes, lo, hi)
    n_first = sum(1 for m in mods if m[0].startswith(prefixes[0]))
    stacks = getattr(tr, "stacks", {})
    out: Dict[str, int] = {}
    for name, s, d in T.self_times(T.ops_inside(tr, "", mods)):
        parts = scopes_of(stacks.get((name, s), ""))
        key = next((p for p in parts if p in SCOPES), "none")
        out[key] = out.get(key, 0) + d
    return out, n_first


def moe_decode_ms(tr, lo: int, hi: int) -> Optional[float]:
    """Device self time of the operations under the ``moe`` scope inside the
    decode-stage programs, over the end-stage executions (the denominator
    of ``decode_stage_device_ms``), in milliseconds."""
    if not getattr(tr, "stacks", None):
        return None
    by, n = scoped_self_ns(tr, DECODE, lo, hi)
    return by.get("moe", 0) / n / 1e6 if n else None
