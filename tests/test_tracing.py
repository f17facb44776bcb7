"""Engine spans, request lifecycle stamps and the model's name scopes.

* Under ``jax.profiler.trace`` a served run leaves ``engine:`` host spans
  nested as the engine documents them: every ``sync`` inside a phase, every
  phase inside a ``step``; prefill spans carry their request's id.
* The stamps are ordered ``submit <= admit <= prefill_done <= first_token``
  for chunked admission, one-shot admission and the virtual clock.
* Spans and scopes change no result: greedy tokens are identical with and
  without an active trace, and a stage program compiled with the scopes
  differs from one compiled without them only in its metadata.
"""

import contextlib
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.hardware import PROFILES
from repro.models.model import build_model
from repro.serving.common import SPAN_PREFIX, Request, VirtualClock
from repro.serving.engine import ServingEngine
from repro.serving.loadgen import WorkloadClass, build_schedule, drive, poisson_arrivals
from repro.serving.stream import EndCloudServingEngine

PHASES = {"drain", "harvest", "prefetch", "replan", "admit", "prefill",
          "resolve", "draft", "activate", "end_stage"}


@pytest.fixture(scope="module")
def moe_model():
    cfg = smoke_config(get_config("switch-base")).replace(num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(n, seed=0, lo=4, hi=30):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 500, size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _engine(model, params, **kw):
    kw = {"max_batch": 4, "max_len": 64, "force_split": 2, "prefill_chunk": 8, **kw}
    return EndCloudServingEngine(model, params, end_profile=PROFILES["a100"],
                                 cloud_profile=PROFILES["a100"], **kw)


def _serve(eng, prompts, max_new=6):
    reqs = [Request(i, p, max_new_tokens=max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


def _engine_spans(trace_dir):
    """``(name, start_ns, end_ns, ids)`` of every ``engine:`` host span."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                                int(e.start_ns + e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, s):
    """The innermost other span that holds ``s``."""
    best = None
    for o in spans:
        if o is s or not (o[1] <= s[1] and s[2] <= o[2]):
            continue
        if best is None or o[2] - o[1] < best[2] - best[1]:
            best = o
    return best


@pytest.fixture(scope="module")
def traced_run(moe_model, tmp_path_factory):
    model, params = moe_model
    prompts = _prompts(6)
    plain = [r.generated for r in _serve(_engine(model, params), prompts)]
    eng = _engine(model, params)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        reqs = _serve(eng, prompts)
        gc.collect()
    return plain, reqs, _engine_spans(d)


def test_spans_nest_sync_in_phase_in_step(traced_run):
    _, reqs, spans = traced_run
    names = {s[0] for s in spans}
    assert {"step", "sync", "drain", "harvest", "admit", "prefill", "resolve",
            "activate", "end_stage"} <= names
    for s in spans:
        if s[0] == "gc":
            continue
        parent = _parent(spans, s)
        if s[0] == "sync":
            assert parent is not None and parent[0] in PHASES, (s, parent)
        elif s[0] in PHASES:
            assert parent is not None and parent[0] == "step", (s, parent)
    # every prefill chunk is one request's, with its slot; a prompt of n
    # tokens takes ceil(n / 8) chunks
    pre = [s for s in spans if s[0] == "prefill"]
    assert {s[3]["req"] for s in pre} == {r.request_id for r in reqs}
    for r in reqs:
        chunks = [s for s in pre if s[3]["req"] == r.request_id]
        assert len(chunks) == -(-len(r.prompt) // 8)
        assert len({s[3]["slot"] for s in chunks}) == 1
    assert all(s[3]["group"] >= 0 for s in spans if s[0] in ("drain", "end_stage"))


def test_a_tick_is_its_phases(traced_run):
    """The phase spans cover almost all of each step: what is left is the
    loop around them."""
    _, _, spans = traced_run
    steps = [s for s in spans if s[0] == "step"]
    assert steps
    covered = sum(s[2] - s[1] for s in spans if s[0] in PHASES)
    total = sum(s[2] - s[1] for s in steps)
    assert covered <= total and covered > 0.5 * total


def test_gc_collections_are_named(traced_run):
    _, _, spans = traced_run
    gcs = [s for s in spans if s[0] == "gc"]
    assert gcs and all(s[3]["generation"] in (0, 1, 2) for s in gcs)


def test_tokens_identical_with_and_without_a_trace(traced_run):
    plain, reqs, _ = traced_run
    assert [r.generated for r in reqs] == plain
    assert all(len(g) == 6 for g in plain)


def _ordered(r):
    assert r.submit_time <= r.admit_time <= r.prefill_done_time <= r.first_token_time, (
        r.submit_time, r.admit_time, r.prefill_done_time, r.first_token_time)


def test_stamps_ordered_for_chunked_admission(moe_model):
    model, params = moe_model
    # more requests than slots: some wait for admission
    reqs = _serve(_engine(model, params, max_batch=2), _prompts(5, seed=1))
    for r in reqs:
        _ordered(r)
    assert max(r.admit_time - r.submit_time for r in reqs) > 0


def test_stamps_ordered_for_one_shot_admission(moe_model):
    model, params = moe_model
    eng = ServingEngine(model, params, max_batch=2, max_len=64)
    for r in _serve(eng, _prompts(4, seed=2)):
        _ordered(r)


def test_stamps_ordered_on_the_virtual_clock(moe_model):
    model, params = moe_model
    eng = _engine(model, params, max_batch=2, timing="modeled", clock=VirtualClock())
    classes = (WorkloadClass("chat", priority=0, weight=1.0, prompt_len=(4, 24),
                             new_tokens=(2, 5)),)
    reqs = drive(eng, build_schedule(poisson_arrivals(8, 40.0, seed=3), classes, seed=4))
    assert len(reqs) == 8
    for r in reqs:
        _ordered(r)
        # the modeled schedule books a prefill from its arrival, and the
        # first token exists when the last chunk drains the cloud stage
        assert r.admit_time == r.submit_time
        assert r.first_token_time == r.prefill_done_time


def _strip(hlo: str) -> str:
    body = hlo[hlo.index("\n%"):]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


def test_scopes_change_only_metadata(moe_model, monkeypatch):
    """The decode stages compiled with and without the name scopes are the
    same programs once their metadata is left out; with them, the MoE's,
    attention's and the LM head's operations carry their scope."""
    model, params = moe_model

    def stage_texts():
        eng = _engine(model, params)
        gsz = eng._group_size
        rows = eng.end_pool.device_rows(range(gsz), active=np.zeros((gsz,), bool))
        crow = eng.cloud_pool.device_rows(range(gsz), active=np.zeros((gsz,), bool))
        tok = jnp.zeros((gsz, 1), jnp.int32)
        ln = jnp.zeros((gsz,), jnp.int32)
        eargs = (eng._emask_dev, eng._eres()) if eng._expert_pooled else ()
        end = eng._end_step._fn.lower(eng.end_params, tok, eng._end_pages, rows, ln,
                                      *eargs).compile().as_text()
        z = eng._end_step(eng.end_params, tok, eng._end_pages, rows, ln, *eargs)[0]
        cloud = eng._cloud_step._fn.lower(eng.cloud_params, z, eng._cloud_pages,
                                          crow, ln).compile().as_text()
        return end, cloud

    scoped = stage_texts()
    joined = "\n".join(scoped)
    for scope in ("/moe/gate/", "/moe/experts/", "/attention/", "/kv_write/",
                  "/lm_head/"):
        assert scope in joined, scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = stage_texts()
    assert "/moe/" not in "\n".join(bare)
    for a, b in zip(scoped, bare):
        assert _strip(a) == _strip(b)
