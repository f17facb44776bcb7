#!/usr/bin/env python3
"""A traced run of one cell, reduced with the engine's own spans and the
model's name scopes (``benchlib/engine_trace.py``), which a benchmark run's
reduction does not read yet.

    python bench/tools/breakdown.py --workload <cell> --seed <n> --seconds <s>
        [--trace-s 3.0] [--keep <file>.xplane.pb.gz]

Prints the run's result line, then one JSON object: per tick the device's
busy and idle time and the host's own time (``host_ms_per_tick``: each
``engine:step`` less its ``engine:sync`` spans), the phases' time per tick
and the share of the ticks they cover, the MoE's device time per decode
step (``moe_decode_ms``), device self time by name scope per decode step
and per prefill chunk (the unscoped part by operation kind), the longest
idle gaps and the idle time by the engine span the host was in, the
host events inside the three longest gaps, the longest garbage
collections (and, by the host clock, every collection of
the whole window by generation), the run's end-to-end numbers with the time
the profiler took to stop (the engine stands still meanwhile), and per
window request its time to first token with its parts by the engine's
stamps.  ``--keep`` saves the
profiler file, gzipped.  Not part of a benchmark run."""

import gc
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def _unscoped_by_kind(tr, prefixes, lo, hi) -> dict:
    """Device self time (ms per execution of the first program) of the
    operations with no scope, by the kind in their name (``copy.138`` ->
    ``copy``)."""
    from benchlib import engine_trace as E
    from benchlib import trace as T

    mods = T.modules_matching(tr, prefixes, lo, hi)
    n = sum(1 for m in mods if m[0].startswith(prefixes[0]))
    out: dict = {}
    for name, s, d in T.self_times(T.ops_inside(tr, "", mods)):
        if not any(p in E.SCOPES for p in E.scopes_of(tr.stacks.get((name, s), ""))):
            kind = name.split(".")[0]
            out[kind] = out.get(kind, 0.0) + d / max(n, 1) / 1e6
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:8])


def host_in_gaps(path: str, tr, n_gaps: int = 3, n_events: int = 10) -> list:
    """For each of the ``n_gaps`` longest idle gaps: its start (ms into the
    window), its length, and the host events that overlap it most (thread,
    name, ms of overlap), from every host plane of the profiler file."""
    from jax.profiler import ProfileData

    from benchlib import trace as T

    lo, hi = T.window(tr)
    gaps = sorted(T.idle_gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:n_gaps]
    found = [dict() for _ in gaps]
    for plane in ProfileData.from_file(path).planes:
        if T._is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                s0, s1 = int(e.start_ns), int(e.start_ns + e.duration_ns)
                for i, (a, b) in enumerate(gaps):
                    ov = min(s1, b) - max(s0, a)
                    if ov > 0:
                        key = f"{line.name[:24]}|{e.name.split('#')[0][:60]}"
                        found[i][key] = found[i].get(key, 0) + ov
    return [{"at_ms": (a - lo) / 1e6, "ms": (b - a) / 1e6,
             "host": [[k, v / 1e6] for k, v in sorted(f.items(), key=lambda kv: -kv[1])[:n_events]]}
            for (a, b), f in zip(gaps, found)]


def requests(win) -> list:
    """Per window request: prompt length, and in seconds the time to first
    token as the harness measures it (due to first token) and its parts by
    the engine's stamps: admission wait, prefill, activation."""
    out = []
    for r in win.recs:
        h = r.handle
        row = {"prompt": len(r.req.prompt), "due_s": round(r.due - win.t0, 3),
               "ttft_s": (r.token_times[0] - r.due) if r.token_times else None}
        if getattr(h, "prefill_done_time", None) is not None:
            row.update(wait_s=h.admit_time - h.submit_time,
                       prefill_s=h.prefill_done_time - h.admit_time,
                       activation_s=h.first_token_time - h.prefill_done_time)
        out.append(row)
    return out


def report(tr) -> dict:
    from benchlib import engine_trace as E
    from benchlib import trace as T

    lo, hi = T.window(tr)
    per = {}
    for label, prefixes in (("decode_step", E.DECODE), ("prefill_chunk", E.PREFILL)):
        by, n = E.scoped_self_ns(tr, prefixes, lo, hi)
        if n:
            per[label] = {k: v / n / 1e6 for k, v in sorted(by.items(), key=lambda kv: -kv[1])}
            per[label]["n"] = n
            per[label]["none_by_kind"] = _unscoped_by_kind(tr, prefixes, lo, hi)
    steps = E.spans_named(tr, "step", lo, hi)
    gcs = sorted(E.spans_named(tr, "gc", lo, hi), key=lambda s: -s[2])
    # device busy and idle time inside the ticks, per tick
    busy = sum(T.busy_ns(tr, s[1], s[1] + s[2]) for s in steps)
    tick_ns = sum(s[2] for s in steps)
    return {
        "window_s": (hi - lo) / 1e9,
        "ticks": len(steps),
        "tick_ms": tick_ns / max(len(steps), 1) / 1e6,
        "busy_ms_per_tick": busy / max(len(steps), 1) / 1e6,
        "idle_ms_per_tick": (tick_ns - busy) / max(len(steps), 1) / 1e6,
        "host_ms_per_tick": E.host_ms_per_tick(tr, lo, hi),
        "phase_share": E.phase_share(tr, lo, hi),
        "phases": E.phase_ms_per_tick(tr, lo, hi),
        "moe_decode_ms": E.moe_decode_ms(tr, lo, hi),
        "scope_ms": per,
        "idle_gaps": E.top_gaps(tr, lo, hi),
        "idle_s_by_label": E.idle_by_label(tr, lo, hi),
        "gc_ms": [[s[3].get("generation"), s[2] / 1e6] for s in gcs[:10]],
        "ops_with_stack": sum(1 for e in tr.ops if (e[0], e[1]) in tr.stacks) / max(len(tr.ops), 1),
    }


if __name__ == "__main__":
    import argparse

    from benchlib import engine_trace, harness, stats
    from benchlib import trace as T

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-s", type=float, default=harness.TRACE_S,
                    help="length of the traced stretch at the window's end")
    ap.add_argument("--keep", help="where to save the profiler file, gzipped")
    a = ap.parse_args()
    harness.TRACE_S = a.trace_s
    kept = Path(tempfile.mkdtemp(prefix="breakdown_")) / "trace.xplane.pb"
    find = T.find_xplane

    def find_and_copy(trace_dir):
        path = find(trace_dir)
        shutil.copyfile(path, kept)
        return path

    T.find_xplane = find_and_copy  # the harness deletes its trace directory
    import jax

    stop = jax.profiler.stop_trace
    stop_s = []

    def timed_stop():
        t = time.perf_counter()
        stop()
        stop_s.append(time.perf_counter() - t)

    jax.profiler.stop_trace = timed_stop  # the engine stands still meanwhile
    collections = []  # (generation, start, seconds) of every gc pass, host clock
    gc_start = [0.0]

    def gc_timer(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            collections.append((info["generation"], gc_start[0],
                                time.perf_counter() - gc_start[0]))

    gc.callbacks.append(gc_timer)
    err = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    t0 = time.perf_counter()
    win = {}
    out = harness.run_cell(a.workload, a.seed, a.seconds, True, root=HERE.parent,
                           t_start=t0, log=err, hooks={"window": lambda w: win.update(w=w)})
    print(json.dumps(out), flush=True)
    tr = engine_trace.load(str(kept))
    rep = report(tr)
    rep["host_in_gaps"] = host_in_gaps(str(kept), tr)
    w = win["w"]
    # the end-to-end numbers of this traced run, to set beside an untraced
    # run of the same seed
    rep["traced_run"] = {
        "output_tok_s": stats.tokens_between(w, w.t0, w.t1) / (w.t1 - w.t0),
        "itl_p95_ms": stats.percentile(stats.itls(w), 95) * 1e3,
        "ttft_p95_s": stats.percentile(stats.ttfts(w), 95),
        "stop_trace_s": stop_s,
    }
    # the garbage collector over the whole window, traced or not
    by_gen: dict = {}
    for g, t, d in collections:
        if w.t0 <= t < w.t1:
            e = by_gen.setdefault(g, {"n": 0, "total_ms": 0.0, "max_ms": 0.0})
            e["n"] += 1
            e["total_ms"] += d * 1e3
            e["max_ms"] = max(e["max_ms"], d * 1e3)
    rep["gc_window"] = by_gen
    # every full collection of the run (the harness forces one after the
    # window): what one costs at this heap
    rep["gc_full_ms"] = [[round(t - w.t0, 3), d * 1e3] for g, t, d in collections if g == 2]
    rep["requests"] = requests(w)
    print(json.dumps(rep), flush=True)
    if a.keep:
        with open(kept, "rb") as f, gzip.open(a.keep, "wb", compresslevel=9) as g:
            shutil.copyfileobj(f, g)
    shutil.rmtree(kept.parent)
