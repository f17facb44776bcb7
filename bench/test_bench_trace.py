"""The reduction from a profiler trace to busy time, idle gaps, program and
kernel times, and the readers that use it."""

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from benchlib import driver, harness  # noqa: E402
from benchlib import trace as T  # noqa: E402
from benchlib.traffic import Req  # noqa: E402

# A hand-made trace: window 0-1000 ns; two decode programs and one prefill
# chunk, the kernel inside the first decode program and inside the chunk.
SYN = T.Trace(
    ops=[("fusion.1", 100, 60), ("paged_attention", 160, 40), ("fusion.2", 200, 50),
         ("paged_attention", 420, 25), ("fusion.3", 600, 100), ("copy.1", 900, 50)],
    modules=[("jit_end_step_pooled(1)", 100, 150), ("jit_cloud_step(2)", 400, 60),
             ("jit_end_prefill_chunk_pooled(3)", 600, 100),
             ("jit_cloud_prefill_chunk(4)", 900, 50)],
    spans=[("window", 0, 1000), ("step", 50, 400), ("wait", 460, 100),
           ("step", 560, 440), ("submit", 880, 10)],
)


def test_busy_time_is_the_union_of_operations():
    # 100-250 (merged), 420-445, 600-700, 900-950
    assert T.busy_ns(SYN, 0, 1000) == 150 + 25 + 100 + 50
    assert T.busy_ns(SYN, 200, 650) == 50 + 25 + 50


def test_idle_gaps_and_their_labels():
    gaps = T.idle_gaps(SYN, 0, 1000)
    assert gaps == [(0, 100), (250, 420), (445, 600), (700, 900), (950, 1000)]
    assert T.span_at(SYN, 10) == "other"
    assert T.span_at(SYN, 500) == "wait"
    assert T.span_at(SYN, 885) == "submit"  # the innermost span
    top = T.top_gaps(SYN, 0, 1000, n=2)
    assert top == [["step", 200e-9], ["step", 170e-9]]


def test_self_time_leaves_out_nested_operations():
    ev = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 50, 20),
          ("copy.1", 200, 5)]
    assert T.self_times(ev) == [("while.1", 0, 50), ("fusion.1", 10, 30),
                                ("fusion.2", 50, 20), ("copy.1", 200, 5)]
    tr = T.Trace(ops=ev)
    assert T.top_ops(tr, 0, 300, n=2) == [["while.1", 50e-9], ["fusion.1", 30e-9]]


def test_top_operations_by_device_time():
    assert T.top_ops(SYN, 0, 1000, n=3) == [["fusion.3", 100e-9],
                                            ["paged_attention", 65e-9], ["fusion.1", 60e-9]]


def test_programs_and_kernels_inside_them():
    dec = T.modules_matching(SYN, ("jit_end_step", "jit_cloud_step"), 0, 1000)
    assert [m[0] for m in dec] == ["jit_end_step_pooled(1)", "jit_cloud_step(2)"]
    k = T.ops_inside(SYN, "paged_attention", dec)
    assert [e[1] for e in k] == [160, 420]
    pre = T.modules_matching(SYN, ("jit_end_prefill_chunk",), 0, 1000)
    assert T.ops_inside(SYN, "paged_attention", pre) == []
    assert T.window(SYN) == (0, 1000)


def _run_data(trace=SYN):
    r = driver.Rec(Req(0, np.zeros(100, np.int32), 3), due=0.0)
    r.token_times = [0.1, 0.2, 0.3]
    w = driver.Window(0.0, 1.0, [r], [driver.Tick(0.0, 0.05, "step")], 1.0)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    conf = {"num_layers": 2, "num_heads": 2, "num_kv_heads": 2, "head_dim": 4}
    run = harness.RunData(w, 1.0, "open", conf, peaks, trace=trace,
                          trace_window=(0, 1000), trace_window_s=(0.0, 1.0))
    return run


def _reader(name):
    return harness.load_module(HERE / "metrics" / f"{name}.py", f"t_{name}")


def test_trace_readers_on_the_hand_made_trace():
    run = _run_data()
    assert _reader("device_idle_share").read(run) == pytest.approx(100 * (1 - 325 / 1000))
    # decode programs: (150 + 60) ns over 1 end execution
    assert _reader("decode_stage_device_ms").read(run) == pytest.approx(210e-6)
    assert _reader("prefill_chunk_device_ms").read(run) == pytest.approx(150e-6)
    # tokens 2 and 3 at 101 and 102 keys: bytes bound the roofline
    b = sum(2 * (2 * c * 2 * 4 * 2 + 2 * 2 * 4 * 2) for c in (101, 102))
    want = 100 * (b / 819e9) / 65e-9
    assert _reader("paged_attn_roofline").read(run) == pytest.approx(want)


def test_trace_readers_find_nothing_without_a_trace():
    run = _run_data(trace=None)
    for name in ("device_idle_share", "decode_stage_device_ms",
                 "prefill_chunk_device_ms", "paged_attn_roofline"):
        assert _reader(name).read(run) is None


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """A 0.1 s trace of ``sb8-chat-poisson`` recorded on a TPU v5e by a
    ``--trace 1`` run with a traced stretch of 0.15 s, its profiler file
    kept (gzipped)."""
    import gzip
    import shutil

    p = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    with gzip.open(HERE / "fixtures" / "v5e_sb8_trace.xplane.pb.gz") as f, open(p, "wb") as g:
        shutil.copyfileobj(f, g)
    return T.load(str(p))


def test_chip_trace_reduces_to_what_the_run_reported(chip_trace):
    tr = chip_trace
    lo, hi = T.window(tr)
    # the run's own line said window_s 0.100930015, busy_s 0.07660206
    assert (hi - lo) == 100930015
    assert T.busy_ns(tr, lo, hi) == 76602060
    assert len(tr.ops) == 7000 and all(not n.startswith("%") for n, _, _ in tr.ops)
    assert sorted({s[0] for s in tr.spans}) == ["step", "window"]
    dec = T.modules_matching(tr, ("jit_end_step", "jit_cloud_step"), lo, hi)
    assert sorted(m[0].split("(")[0] for m in dec) == [
        "jit_cloud_step", "jit_cloud_step", "jit_end_step_pooled", "jit_end_step_pooled"]
    # one kernel call per attention layer of each tier's 3 blocks x 2 layers
    assert len(T.ops_inside(tr, "paged_attention", dec)) == 4 * 6
    run = _run_data(trace=tr)
    run.trace_window = (lo, hi)
    assert _reader("prefill_chunk_device_ms").read(run) == pytest.approx(18.2765625)
    assert _reader("decode_stage_device_ms").read(run) == pytest.approx(20.026878)
    assert _reader("device_idle_share").read(run) == pytest.approx(100 * (1 - 76602060 / 100930015))
    gaps = T.top_gaps(tr, lo, hi)
    assert len(gaps) == 10 and all(label == "step" for label, _ in gaps)
    assert sum(s for _, s in T.top_ops(tr, lo, hi, n=10**6)) <= (hi - lo) / 1e9
