"""The reduction of the program's own instrumentation in a profiler trace
(``benchlib/xplane.py``, ``benchlib/engine_trace.py``) and the readers of
the engine's request stamps: on hand-made traces and windows, on the
recorded chip trace the older readers are tested on, and on a small traced
run on the CPU."""

import gzip
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import driver, harness, stamps, xplane  # noqa: E402
from benchlib import engine_trace as E  # noqa: E402
from benchlib import trace as T  # noqa: E402
from benchlib.traffic import Req  # noqa: E402

FIXTURE = HERE / "fixtures" / "v5e_sb8_trace.xplane.pb.gz"


def _gunzip(src: Path, tmp: Path) -> str:
    p = tmp / (src.name[:-3])
    with gzip.open(src) as f, open(p, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(p)


@pytest.fixture(scope="module")
def old_fixture(tmp_path_factory):
    return _gunzip(FIXTURE, tmp_path_factory.mktemp("old"))


# -- the name-stack reader ------------------------------------------------


def test_name_stacks_read_without_tensorflow(old_fixture):
    buf = Path(old_fixture).read_bytes()
    assert "/device:TPU:0" in xplane.plane_names(buf)
    st = xplane.op_stacks(buf, "/device:TPU:0")
    assert len(st) == 7000
    stacks = [s for _, s in st]
    assert stacks.count("jit(end_step_pooled)/while/body/closed_call/gather:") == 816
    attn = {s for n, s in st if n.startswith("%paged_attention.22 ")}
    assert attn == {
        "jit(end_prefill_chunk_pooled)/while/body/closed_call/jit(paged_attention)/"
        "paged_attention/pallas_call:",
        "jit(cloud_step)/while/body/closed_call/jit(paged_attention)/"
        "paged_attention/pallas_call:"}
    # hoisted casts and loops carry no stack
    assert {s for n, s in st if n.startswith(("%convert.94 ", "%while."))} == {""}
    assert not any(m.startswith("tensorflow") for m in sys.modules)
    assert xplane.op_stacks(buf, "/device:TPU:9") == []


def test_wire_format_fields():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32 7, field 4 fixed64 9
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 2, ord("a"), ord("b"), 0x1D, 7, 0, 0, 0,
                 0x21, 9, 0, 0, 0, 0, 0, 0, 0])
    got = list(xplane.fields(buf))
    assert got == [(1, 0, 300), (2, 2, (5, 7)), (3, 5, 7), (4, 1, 9)]
    with pytest.raises(ValueError):
        list(xplane.fields(bytes([0x0B])))  # group start: not read


# -- the older readers read the same through the new loader -----------------


def _run_data(trace, lo_hi):
    r = driver.Rec(Req(0, np.zeros(100, np.int32), 3), due=0.0)
    r.token_times = [0.1, 0.2, 0.3]
    w = driver.Window(0.0, 1.0, [r], [driver.Tick(0.0, 0.05, "step")], 1.0)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    conf = {"num_layers": 2, "num_heads": 2, "num_kv_heads": 2, "head_dim": 4,
            "d_model": 8, "d_ff": 16, "vocab_size": 32,
            "moe": {"num_experts": 4, "top_k": 1, "d_ff_expert": 16},
            "layer_pattern": [{"kind": "attn", "moe": True}]}
    return harness.RunData(w, 1.0, "open", conf, peaks, trace=trace,
                           trace_window=lo_hi, trace_window_s=(0.0, 1.0))


OLDER = ("device_idle_share", "decode_stage_device_ms", "prefill_chunk_device_ms",
         "paged_attn_roofline", "tick_ms_mean", "ttft_p95_s", "output_tok_s",
         "itl_p95_ms", "gen_lag_ms_p95", "setup_s")


@pytest.mark.parametrize("name", OLDER)
def test_older_reader_reads_the_same_through_the_new_loader(old_fixture, name):
    base, ext = T.load(old_fixture), E.load(old_fixture)
    assert (ext.ops, ext.modules, ext.spans) == (base.ops, base.modules, base.spans)
    w = T.window(base)
    assert T.window(ext) == w
    mod = harness.load_module(HERE / "metrics" / f"{name}.py", f"e_{name}")
    assert mod.read(_run_data(ext, w)) == mod.read(_run_data(base, w))


def test_breakdown_reads_the_same_where_the_engine_wrote_no_span(old_fixture):
    base, ext = T.load(old_fixture), E.load(old_fixture)
    lo, hi = T.window(base)
    assert ext.engine == [] and len(ext.stacks) == 5472
    assert T.top_ops(ext, lo, hi) == T.top_ops(base, lo, hi)
    assert E.top_gaps(ext, lo, hi) == T.top_gaps(base, lo, hi)
    # the recorded program set no scope: no operation is the MoE's
    by, n = E.scoped_self_ns(ext, E.DECODE, lo, hi)
    assert n == 2 and set(by) == {"none"}
    assert E.moe_decode_ms(ext, lo, hi) == 0.0
    assert E.host_ms_per_tick(ext, lo, hi) is None


# -- arithmetic on a hand-made trace ----------------------------------------

# window 0-1000 ns: two ticks; the first drains, admits and prefills, the
# second runs an end stage; device operations inside two decode programs
SYN = E.EngineTrace(
    ops=[("while.1", 100, 100), ("fusion.1", 110, 40), ("fusion.2", 160, 30),
         ("copy.1", 210, 20), ("fusion.3", 600, 50), ("fusion.4", 700, 40)],
    modules=[("jit_end_step(1)", 100, 140), ("jit_cloud_step(2)", 600, 100),
             ("jit_end_prefill_chunk(3)", 700, 40)],
    spans=[("window", 0, 1000), ("step", 50, 400), ("step", 500, 400)],
    engine=[("step", 50, 400, {}), ("drain", 60, 150, {"group": 0}),
            ("sync", 100, 100, {}), ("admit", 260, 20, {}),
            ("prefill", 300, 120, {"req": 7, "slot": 1}), ("sync", 320, 60, {}),
            ("gc", 430, 10, {"generation": 0}),
            ("step", 500, 400, {}), ("end_stage", 510, 380, {"group": 1}),
            ("sync", 600, 150, {})],
    stacks={("fusion.1", 110): "jit(end_step)/while/body/moe/experts/dot_general:",
            ("fusion.2", 160): "jit(end_step)/while/body/attention/dot_general:",
            ("fusion.3", 600): "jit(cloud_step)/while/body/moe/gate/dot_general:",
            ("fusion.4", 700): "jit(end_prefill_chunk)/while/body/moe/x:"},
)


def test_host_time_per_tick_leaves_out_the_syncs():
    # (400 - 100 - 60) and (400 - 150) ns
    assert E.host_ms_per_tick(SYN, 0, 1000) == pytest.approx((240 + 250) / 2 / 1e6)
    assert E.host_ms_per_tick(SYN, 0, 460) == pytest.approx(240 / 1e6)
    assert E.host_ms_per_tick(SYN, 0, 40) is None
    assert E.host_ms_per_tick(T.Trace(), 0, 1000) is None


def test_phases_per_tick_and_their_cover():
    ph = E.phase_ms_per_tick(SYN, 0, 1000)
    assert ph["drain"] == {"ms": 150 / 2 / 1e6, "sync_ms": 100 / 2 / 1e6, "n_per_tick": 0.5}
    assert ph["prefill"]["sync_ms"] == pytest.approx(30e-6)
    assert ph["gc"]["n_per_tick"] == 0.5
    assert E.phase_share(SYN, 0, 1000) == pytest.approx(100 * (150 + 20 + 120 + 380) / 800)


def test_gaps_are_labelled_by_the_innermost_engine_span():
    assert E.label_at(SYN, 250) == "engine:step"
    assert E.label_at(SYN, 270) == "engine:admit"
    assert E.label_at(SYN, 350) == "engine:sync"
    assert E.label_at(SYN, 435) == "engine:gc"
    assert E.label_at(SYN, 470) == "other"  # the harness's own label
    assert E.label_at(SYN, 20) == "other"
    # gaps: 0-100, 200-210, 230-600, 650-700, 740-1000
    assert E.top_gaps(SYN, 0, 1000, n=2) == [["engine:prefill", pytest.approx(370e-9)],
                                             ["engine:end_stage", pytest.approx(260e-9)]]
    idle = E.idle_by_label(SYN, 0, 1000)
    assert idle == pytest.approx({"engine:prefill": 370e-9, "engine:end_stage": 260e-9,
                                  "engine:step": 100e-9, "engine:sync": 50e-9,
                                  "engine:drain": 10e-9})
    assert list(idle)[:2] == ["engine:prefill", "engine:end_stage"]


def test_device_time_by_scope():
    assert E.scopes_of("jit(f)/while/body/moe/experts/dot_general:") == [
        "jit(f)", "while", "body", "moe", "experts", "dot_general"]
    assert E.scopes_of("") == []
    by, n = E.scoped_self_ns(SYN, E.DECODE, 0, 1000)
    # while.1 holds fusion.1 and fusion.2: its self time is 30 ns
    assert n == 1
    assert by == {"none": 30 + 20, "moe": 40 + 50, "attention": 30}
    assert E.moe_decode_ms(SYN, 0, 1000) == pytest.approx(90e-6)
    assert E.moe_decode_ms(T.Trace(ops=SYN.ops, modules=SYN.modules), 0, 1000) is None
    by, n = E.scoped_self_ns(SYN, E.PREFILL, 0, 1000)
    assert (by, n) == ({"moe": 40}, 1)


# -- the readers of the engine's stamps --------------------------------------


class _Handle:
    def __init__(self, submit, admit=None, done=None):
        self.submit_time, self.admit_time, self.prefill_done_time = submit, admit, done


def _stamped_run(handles, ticks=(), trace_s=(0.0, 0.0)):
    recs = []
    for i, h in enumerate(handles):
        r = driver.Rec(Req(i, np.zeros(4, np.int32), 2), due=h.submit_time)
        # the harness's clock runs 100 s ahead of the engine's
        r.handle, r.sent = h, h.submit_time + 100.0
        recs.append(r)
    w = driver.Window(100.0, 101.0, recs, list(ticks), 101.0)
    return harness.RunData(w, 1.0, "open", {}, {}, trace_window_s=trace_s)


def test_stamp_readers():
    q = harness.load_module(HERE / "metrics" / "queue_wait_s_p95.py", "t_q")
    p = harness.load_module(HERE / "metrics" / "prefill_s_p95.py", "t_p")
    hs = [_Handle(0.0, 0.5, 2.5), _Handle(1.0, 1.0, 1.5), _Handle(2.0, 4.0, None),
          _Handle(3.0)]
    run = _stamped_run(hs)
    assert q.read(run) == pytest.approx(np.percentile([0.5, 0.0, 2.0], 95))
    assert p.read(run) == pytest.approx(np.percentile([2.0, 0.5], 95))
    # an engine without the stamps (the request type of an older program)
    run = _stamped_run([type("R", (), {"submit_time": 0.0})()])
    assert q.read(run) is None and p.read(run) is None


def test_stamp_readers_leave_out_the_profilers_stall():
    p = harness.load_module(HERE / "metrics" / "prefill_s_p95.py", "t_p2")
    # the trace stops at 101.5 s (harness clock) and the engine ticks again
    # at 104.5: prefill from 101 to 106 (engine clock 1-6) took 2 s
    ticks = [driver.Tick(101.4, 101.5, "drain"), driver.Tick(104.5, 104.6, "drain")]
    hs = [_Handle(0.5, 1.0, 6.0), _Handle(0.0, 0.0, 1.0)]
    run = _stamped_run(hs, ticks, trace_s=(98.5, 101.5))
    assert stamps.profiler_stall(run) == (101.5, 104.5)
    assert sorted(stamps.durations(run, "admit_time", "prefill_done_time")) == \
        pytest.approx([1.0, 2.0])
    assert p.read(run) == pytest.approx(np.percentile([1.0, 2.0], 95))
    # untraced: nothing left out
    run = _stamped_run(hs, ticks)
    assert stamps.profiler_stall(run) == (0.0, 0.0)
    assert p.read(run) == pytest.approx(np.percentile([1.0, 5.0], 95))


# -- a small traced run on the CPU -----------------------------------------


def test_small_traced_run_carries_engine_spans_and_stamps(tmp_path, monkeypatch):
    from test_bench_serve import SEED, _small  # noqa: E402

    from repro.models import attention

    kept = tmp_path / "run.xplane.pb"
    find = T.find_xplane

    def find_and_copy(d):
        path = find(d)
        shutil.copyfile(path, kept)
        return path

    monkeypatch.setattr(T, "find_xplane", find_and_copy)
    attention.set_paged_attention_impl("kernel")
    try:
        out = harness.run_cell("sb8-chat-poisson", SEED, 4.0, True, root=ROOT,
                               t_start=time.perf_counter(), require_tpu=False,
                               override=_small(), log=lambda *a: None)
    finally:
        attention.set_paged_attention_impl(None)
    assert out["correct"], out["compared"]
    m = out["metrics"]
    assert 0 <= m["queue_wait_s_p95"]["value"] < m["prefill_s_p95"]["value"] + 4.0
    assert m["prefill_s_p95"]["value"] > 0
    tr = E.load(str(kept))
    lo, hi = T.window(tr)
    names = {s[0] for s in tr.engine}
    assert {"step", "sync", "admit", "prefill", "end_stage", "drain"} <= names
    ticks = E.spans_named(tr, "step", lo, hi)
    assert ticks and 0 < E.host_ms_per_tick(tr, lo, hi) <= max(s[2] for s in ticks) / 1e6
    assert 50 < E.phase_share(tr, lo, hi) <= 100


# -- a trace recorded on the chip with the engine's spans and the scopes ----


@pytest.fixture(scope="module")
def engine_fixture(tmp_path_factory):
    """0.1 s of ``sb8-chat-poisson`` on a TPU v5e, two requests decoding,
    from ``bench/tools/breakdown.py --seconds 10 --trace-s 0.14 --keep``."""
    path = _gunzip(HERE / "fixtures" / "v5e_sb8_engine_trace.xplane.pb.gz",
                   tmp_path_factory.mktemp("new"))
    return E.load(path)


def test_chip_trace_host_time_per_tick(engine_fixture):
    tr = engine_fixture
    lo, hi = T.window(tr)
    assert hi - lo == 99813825 and len(tr.ops) == 4292
    assert {s[0] for s in tr.engine} == {"step", "drain", "harvest", "prefetch", "replan",
                                         "admit", "resolve", "activate", "end_stage", "sync"}
    assert len(E.spans_named(tr, "step", lo, hi)) == 2
    assert E.host_ms_per_tick(tr, lo, hi) == pytest.approx(4.628828)
    assert E.phase_share(tr, lo, hi) > 99.8
    ph = E.phase_ms_per_tick(tr, lo, hi)
    # the waits for the two stage calls are most of the tick
    assert ph["drain"]["sync_ms"] > 20 and ph["end_stage"]["sync_ms"] > 20
    assert ph["harvest"]["n_per_tick"] == 1.0


def test_chip_trace_moe_device_time(engine_fixture):
    tr = engine_fixture
    lo, hi = T.window(tr)
    assert E.moe_decode_ms(tr, lo, hi) == pytest.approx(2.534253)
    by, n = E.scoped_self_ns(tr, E.DECODE, lo, hi)
    assert n == 2
    assert set(by) == {"none", "attention", "moe", "lm_head", "mlp", "kv_write"}
    # the MoE is below the whole decode stage, which the older reader reads
    run = _run_data(tr, (lo, hi))
    dec = harness.load_module(HERE / "metrics" / "decode_stage_device_ms.py", "f_dec")
    assert E.moe_decode_ms(tr, lo, hi) < dec.read(run)
    assert sum(by.values()) / n / 1e6 <= dec.read(run)
    kernel = [s for (name, _), s in tr.stacks.items() if name.startswith("paged_attention")]
    assert kernel and all("/attention/" in s for s in kernel)


def test_chip_trace_gaps_carry_engine_labels(engine_fixture):
    tr = engine_fixture
    lo, hi = T.window(tr)
    gaps = E.top_gaps(tr, lo, hi)
    assert len(gaps) == 10 and all(label.startswith("engine:") for label, _ in gaps)
    # the harness's reduction still labels them by its own span
    assert {label for label, _ in T.top_gaps(tr, lo, hi)} <= {"step", "other"}
    idle = E.idle_by_label(tr, lo, hi)
    assert sum(idle.values()) == pytest.approx((hi - lo - T.busy_ns(tr, lo, hi)) / 1e9)
