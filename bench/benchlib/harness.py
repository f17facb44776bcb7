"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is
found by name under ``bench/``:

- ``BENCHMARK.json``: the cell's configuration and traffic names, and which
  metrics it reports;
- ``bench/cells/<cell>.json``: the deployment (the engine by its name in
  ``repro.serving`` and the arguments it is built with: split, batch,
  length, the devices both tiers stand for; the end tier's experts) and the
  limits of the check;
- ``bench/configs/<config>.json``: the model's sizes, with the name of its
  plain reference in ``bench/references/``;
- ``bench/traffic/<traffic>.json``: the mix's parameters, read by the one
  generator (``benchlib/traffic.py``), whose arrival processes and length
  distributions are modules under ``bench/generator/``, found by name;
- ``bench/metrics/<metric>.py`` and ``bench/costs/<name>.py``: one reader
  per metric, one cost function per kernel or model count.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from benchlib import check, driver, manifest, stats, traffic
from benchlib import trace as T

BENCH = Path(__file__).resolve().parents[1]

TRACE_S = 3.0  # length of the traced stretch at the end of a --trace 1 window
DRAIN_S = 200.0  # how long the window's requests may take to finish after it
# The set-up's warm-up: ``max_batch`` requests of this prompt length and this
# many new tokens fill every decode slot and run prefill chunks, which
# compiles every program the window calls.
WARMUP_PROMPT_LEN = 40
WARMUP_NEW_TOKENS = 4


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class RunData:
    """What a metric reader sees."""

    window: driver.Window
    setup_s: float
    loop: str
    model: Dict
    peaks: Dict
    trace: Optional[T.Trace] = None
    trace_window: tuple = (0, 0)  # ns, profiler clock
    trace_window_s: tuple = (0.0, 0.0)  # s, harness clock
    _costs: Dict = field(default_factory=dict)

    def cost(self, name: str):
        if name not in self._costs:
            self._costs[name] = load_module(BENCH / "costs" / f"{name}.py",
                                            f"bench_cost_{name}")
        return self._costs[name]


def read_metrics(run: RunData, metrics: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name']}")
        v = mod.read(run)
        if v is not None and np.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(jax, chips: int, peaks_table: Dict, require_tpu: bool) -> Dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if require_tpu:
        if d.platform != "tpu":
            raise NoDevice(f"needs a TPU; JAX found {d.platform!r}")
        if len(devs) < chips:
            raise NoDevice(f"the cell asks for {chips} chips; JAX found {len(devs)}")
        if d.device_kind not in peaks_table["devices"]:
            raise KeyError(f"device kind {d.device_kind!r} is not in bench/peaks.json")
    return info


class CompileCounter:
    """Counts XLA compilations and sums JAX's own durations by event, from
    ``jax.monitoring`` (listeners cannot be removed, so one per process)."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        self.durations: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        self.durations[event] = self.durations.get(event, 0.0) + secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> Dict[str, float]:
        d, self.durations = self.durations, {}
        return d


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path, t_start: float, require_tpu: bool = True,
             log: Callable = print, hooks: Optional[Dict] = None,
             override: Optional[Dict] = None, control: bool = False,
             engine_kw: Optional[Dict] = None) -> Dict:
    """Run one cell; return the result line's object.  For tests:
    ``hooks["engine"]`` is called with the engine after set-up, and
    ``hooks["window"]`` with the window's records, and ``override``
    replaces the cell's ``config``, ``traffic`` or
    ``deployment`` dicts.  For the calibration of the check (never in a
    benchmark run): ``control`` also reads the gaps of the tokens the
    reference computed in the control's precision puts first, and
    ``engine_kw`` passes options to the engine."""
    bench = manifest.load(root)
    cell = manifest.cell(bench, workload)
    override = override or {}
    conf = override.get("config") or manifest.config(bench, cell["config"], root)
    mix = override.get("traffic") or manifest.traffic(cell["traffic"], root)
    dep = override.get("deployment") or manifest.deployment(workload, root)
    args = dep["engine_args"]
    peaks_table = json.loads((BENCH / "peaks.json").read_text())
    manifest.require_system(root)

    import jax

    from benchlib import system

    counter = CompileCounter(jax)
    dev = device_info(jax, int(cell["chips"]), peaks_table, require_tpu)
    peaks = peaks_table["devices"].get(dev["kind"], {})
    if require_tpu:
        from repro.launch.cache import enable_compile_cache

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev}")
    split: Dict[str, float] = {"import_init": time.perf_counter() - t_start}

    # -- weights and engine -------------------------------------------------
    ref = load_module(BENCH / "references" / f"{conf['reference']}.py",
                      f"bench_ref_{conf['reference']}")
    cfg = system.model_config(conf)
    model = system.build_model(cfg)
    key = manifest.prng_key(jax, seed)
    t = time.perf_counter()
    params = ref.make_params(system.param_shapes(model, key), conf, key)
    jax.block_until_ready(params)
    split["params"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = system.build_engine(model, params, dep, **(engine_kw or {}))
    split["engine"] = time.perf_counter() - t
    jd = counter.take()

    # -- the harness's warm-up: every shape the window uses ----------------
    t = time.perf_counter()
    warm = traffic.warmup_requests(seed, conf["vocab_size"],
                                   n=int(args["max_batch"]),
                                   prompt_len=WARMUP_PROMPT_LEN,
                                   max_new=WARMUP_NEW_TOKENS)
    wd = driver.Driver(lambda r: _submit(eng, r), eng.step)
    wd.run(seconds=0.0, drain_s=600.0, pool=warm, clients=len(warm))
    if not system.idle(eng):
        raise RuntimeError("the engine did not drain its warm-up traffic")
    split["warmup"] = time.perf_counter() - t
    jd2 = counter.take()
    compiles_setup = counter.compiles
    if hooks and "engine" in hooks:
        hooks["engine"](eng)
    experts_before = system.end_experts(eng)

    # -- the window -----------------------------------------------------------
    tracing = {"on": False, "dir": None, "ann": None, "t": (0.0, 0.0)}
    annotate = (lambda label: jax.profiler.TraceAnnotation(T.SPAN_PREFIX + label)
                if tracing["on"] else nullcontext())

    def on_tick(d, now):
        if not traced:
            return
        if (not tracing["on"] and tracing["dir"] is None
                and now >= d.t_end - min(TRACE_S, seconds / 2)):
            tracing["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python call tracing costs the host most
            jax.profiler.start_trace(tracing["dir"], profiler_options=opts)
            tracing["on"] = True
            tracing["ann"] = jax.profiler.TraceAnnotation(T.SPAN_PREFIX + "window")
            tracing["ann"].__enter__()
            tracing["t"] = (time.perf_counter(), 0.0)
        elif tracing["on"] and now >= d.t_end:
            tracing["ann"].__exit__(None, None, None)
            tracing["t"] = (tracing["t"][0], time.perf_counter())
            jax.profiler.stop_trace()
            tracing["on"] = False

    drv = driver.Driver(lambda r: _submit(eng, r), eng.step, annotate=annotate)
    c0 = system.counters(eng)
    compiles0 = counter.compiles
    t_first = time.perf_counter()
    if mix["loop"] == "open":
        win = drv.run(seconds=seconds, drain_s=DRAIN_S, on_tick=on_tick,
                      schedule=traffic.open_schedule(mix, seed, seconds,
                                                     conf["vocab_size"]))
    else:
        pool = traffic.closed_pool(mix, seed, int(mix["pool_size"]),
                                   conf["vocab_size"])
        win = drv.run(seconds=seconds, drain_s=DRAIN_S, on_tick=on_tick,
                      pool=pool, clients=int(mix["clients"]),
                      preroll_s=float(mix.get("preroll_s", 0.0)))
    if tracing["on"]:  # the window ended without another tick
        on_tick(drv, float("inf"))
    split["preroll"] = win.t0 - t_first
    setup_s = win.t0 - t_start
    if hooks and "window" in hooks:
        hooks["window"](win)
    win.counters_start, win.counters_end = c0, system.counters(eng)
    win.compiles = counter.compiles - compiles0
    experts_after = system.end_experts(eng)
    stats_mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats_mem.get("peak_bytes_in_use", 0))

    served = {id(r): list(r.handle.generated) for r in win.recs}
    n_fail = stats.failed(win)
    log(f"set-up {setup_s:.3f} s: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f"; compiles in set-up {compiles_setup}, "
        f"persistent-cache hits {counter.cache_hits}")
    log("set-up JAX durations (engine): " + json.dumps({k: round(v, 3) for k, v in jd.items()}))
    log("set-up JAX durations (warm-up): " + json.dumps({k: round(v, 3) for k, v in jd2.items()}))
    ttft, itl = stats.ttfts(win), stats.itls(win)
    log(f"window {seconds} s: {len(win.recs)} requests, {n_fail} failed, "
        f"{len(itl)} token gaps, {stats.tokens_between(win, win.t0, win.t1)} tokens in "
        f"window, {len(win.ticks)} ticks ({len(stats.step_ticks(win))} in window), "
        f"drain ended {win.drained_at - win.t1:.3f} s after close; compiles in window "
        f"{win.compiles}; {len(win.pre)} pre-roll requests")
    log(f"medians: ttft {stats.percentile(ttft, 50):.4f} s, itl "
        f"{stats.percentile(itl, 50) * 1e3:.3f} ms, send lag "
        f"{stats.percentile(stats.lags(win), 50) * 1e3:.3f} ms")
    log(f"counters: start {c0} end {win.counters_end}")
    log(f"peak device memory {dev['memory_peak_bytes']} bytes")

    # -- trace --------------------------------------------------------------
    run = RunData(win, setup_s, mix["loop"], conf, peaks)
    breakdown = None
    if traced:
        path = T.find_xplane(tracing["dir"])
        run.trace = T.load(path)
        run.trace_window = T.window(run.trace)
        run.trace_window_s = tracing["t"]
        lo, hi = run.trace_window
        busy = T.busy_ns(run.trace, lo, hi) / 1e9
        dev["busy_s"], dev["window_s"] = busy, (hi - lo) / 1e9
        breakdown = {"device_ops": T.top_ops(run.trace, lo, hi),
                     "idle_gaps": T.top_gaps(run.trace, lo, hi)}
        shutil.rmtree(tracing["dir"], ignore_errors=True)

    metrics = read_metrics(run, manifest.metrics_for(
        bench, workload, "per_layer" if traced else "end_to_end"))

    # -- the check ------------------------------------------------------------
    system.release(eng)
    del eng, drv, wd
    gc.collect()
    reasons = []
    if experts_before != experts_after:
        reasons.append("the end tier's experts changed during the window")
    if experts_before != [sorted(dep["end_experts"])] * len(experts_before):
        reasons.append(f"the end tier's experts {experts_before} are not the "
                       f"deployment's {dep['end_experts']}")
    picked = check.sample(win.recs, seed, int(dep["check_tokens"]))
    if not picked:
        reasons.append("no request finished")
    run_ref = reference_fn(jax, ref, params, conf, dep, control)
    t = time.perf_counter()
    g = check.gaps(picked, lambda *a: run_ref(*a)[0], int(args["max_len"]),
                   lambda r: served[id(r)])
    log(f"check: {len(picked)} requests, {len(g)} served tokens, "
        f"{time.perf_counter() - t:.3f} s; gaps (units of logit std) "
        + ", ".join(f"{k} {f(g):.6g}" for k, f in check.STATS.items()) if len(g) else "")
    lim = dep.get("limits", {})
    compared = check.judge(g, lim) if len(g) else {}
    correct = not reasons and check.passed(compared)
    for r in reasons:
        log(f"check failed: {r}")
    if not lim:
        log("check failed: the cell states no limits")
    for k, c in compared.items():
        log(f"compared {k} {c['value']:.6g} limit {c['limit']:.6g}")
    out = {"correct": bool(correct), "attempted": len(win.recs), "failed": n_fail,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control and len(g):
        g_low = check.gaps(picked, lambda *a: run_ref(*a)[1], int(args["max_len"]),
                           lambda r: served[id(r)])
        out["calibration"] = {
            "program": {k: f(g) for k, f in check.STATS.items()},
            "control": {k: f(g_low) for k, f in check.STATS.items()},
            "n_tokens": int(len(g)),
        }
    out["compared"] = compared
    return out


def tier_split(dep: Dict) -> int:
    """The block where the end tier stops: the engine's forced split, or
    the one split that every end device of a fleet is forced to."""
    args = dep["engine_args"]
    if "force_split" in args:
        return int(args["force_split"])
    splits = set(args["force_splits"])
    if len(splits) != 1:
        raise ValueError(f"the reference needs one split; the cell forces {splits}")
    return int(splits.pop())


def reference_fn(jax, ref, params, conf: Dict, dep: Dict, control: bool = False):
    """``(tokens, targets) -> (gaps, control gaps or None)`` of the float32
    reference, compiled once at the deployment's length."""
    import jax.numpy as jnp

    allowed = jnp.asarray(ref.allowed_mask(conf["moe"]["num_experts"],
                                           dep["end_experts"]))
    split = tier_split(dep)
    rnd = ref.control_round if control else None
    fn = jax.jit(lambda p, t, y: ref.token_gaps(p, conf, t, y, split, allowed, rnd))
    cache: Dict = {}

    def run(toks, tgts):
        key = (toks.tobytes(), tgts.tobytes())
        if key not in cache:
            cache.clear()
            cache[key] = fn(params, jnp.asarray(toks), jnp.asarray(tgts))
        return cache[key]

    return run


def _submit(eng, req):
    from benchlib import system

    h = system.request(req.index, req.prompt, req.max_new)
    eng.submit(h)
    return h


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    root = BENCH.parent
    err = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace), root=root,
                       t_start=t_start, log=err)
    except (NoDevice, manifest.ManifestError) as e:
        err(f"bench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0
