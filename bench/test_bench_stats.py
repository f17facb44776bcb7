"""Window, drain and percentile arithmetic of the harness, on a fake engine
and a fake clock (no JAX)."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import driver, stats  # noqa: E402
from benchlib.traffic import Req  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Handle:
    def __init__(self, req):
        self.req = req
        self.generated = []

    @property
    def done(self):
        return len(self.generated) >= self.req.max_new


class FakeEngine:
    """Each step takes ``tick`` seconds and gives every live request one
    token; a request's first token comes ``prefill_ticks`` steps after it
    was sent."""

    def __init__(self, clock, tick=0.01, prefill_ticks=2, stall=None):
        self.clock, self.tick, self.prefill_ticks = clock, tick, prefill_ticks
        self.live = []
        self.stall = stall  # request index that never finishes

    def submit(self, req):
        h = Handle(req)
        self.live.append([h, 0])
        return h

    def step(self):
        self.clock.t += self.tick
        for item in self.live:
            h, age = item
            item[1] = age + 1
            if age + 1 >= self.prefill_ticks and not h.done:
                if h.req.index == self.stall and len(h.generated) >= 1:
                    continue
                h.generated.append(7)
        self.live = [i for i in self.live if not i[0].done]


def _run(schedule=None, pool=None, clients=0, seconds=1.0, drain_s=5.0, **kw):
    clock = Clock()
    eng = FakeEngine(clock, **kw)
    d = driver.Driver(eng.submit, eng.step, clock=clock, sleep=clock.sleep)
    return d.run(seconds=seconds, drain_s=drain_s, schedule=schedule, pool=pool,
                 clients=clients)


def test_open_loop_stamps_due_times_tokens_and_lag():
    sched = [Req(i, np.zeros(4, np.int32), 3, due_s=0.25 * i) for i in range(4)]
    w = _run(schedule=sched)
    assert [r.req.index for r in w.recs] == [0, 1, 2, 3]
    assert [round(r.due - w.t0, 9) for r in w.recs] == [0.0, 0.25, 0.5, 0.75]
    # the engine idles between requests, so every send is on time
    assert all(abs(r.lag_s) < 1e-9 for r in w.recs)
    # first token after two ticks, then one per tick
    for r in w.recs:
        assert len(r.token_times) == 3
        assert np.allclose(np.diff([r.due] + r.token_times), [0.02, 0.01, 0.01])
    assert stats.failed(w) == 0
    assert np.allclose(stats.ttfts(w), 0.02)
    assert np.allclose(stats.itls(w), 0.01)
    assert stats.tokens_between(w, w.t0, w.t1) == 12


def test_busy_engine_makes_the_generator_late():
    # a 0.3 s tick holds back requests due while it runs
    sched = [Req(i, np.zeros(4, np.int32), 2, due_s=0.1 * i) for i in range(3)]
    w = _run(schedule=sched, tick=0.3, prefill_ticks=1)
    assert [round(r.lag_s, 9) for r in w.recs] == [0.0, 0.2, 0.1]
    # TTFT counts from the due time, so the lag is inside it
    assert np.allclose(stats.ttfts(w), [0.3, 0.5, 0.4])


def test_closed_loop_resends_until_the_window_closes_then_drains():
    pool = [Req(i, np.zeros(4, np.int32), 5) for i in range(100)]
    tick = 1 / 64  # exact in binary, so stamps fall on tick boundaries
    w = _run(pool=pool, clients=2, seconds=16 * tick, tick=tick, prefill_ticks=1)
    # each request takes 5 ticks; two clients for 16 ticks send 4 each (the
    # last pair at tick 15, finishing at tick 20, after the close)
    assert len(w.recs) == 8
    assert all(r.done for r in w.recs)
    assert [r.req.index for r in w.recs] == list(range(8))
    # each resend is due the moment its predecessor finished
    assert np.isclose(w.recs[2].due, w.recs[0].token_times[-1])
    # two tokens a tick; the tick that ends at the close is outside
    assert stats.tokens_between(w, w.t0, w.t1) == 30
    assert all(t.label == "drain" for t in w.ticks if t.start >= w.t1)


def test_drain_limit_counts_unfinished_requests_as_failed():
    sched = [Req(i, np.zeros(4, np.int32), 3, due_s=0.0) for i in range(2)]
    w = _run(schedule=sched, seconds=0.1, drain_s=0.5, stall=1)
    assert stats.failed(w) == 1
    assert w.drained_at > w.t1 + 0.5
    # the stalled request still has its first token; a request with none
    # would count from the drain's end
    assert len(w.recs[1].token_times) == 1


def test_ttft_of_a_request_without_tokens_is_counted_to_the_drain_end():
    w = driver.Window(0.0, 1.0, [driver.Rec(Req(0, np.zeros(1, np.int32), 2), due=0.5)],
                      [], drained_at=3.0)
    assert stats.ttfts(w) == [2.5]
    assert stats.failed(w) == 1


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(101)), 95, 95.0),
    ([5.0], 95, 5.0),
    ([1, 2], 95, 1.95),
])
def test_percentile_interpolates_linearly(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nan():
    assert np.isnan(stats.percentile([], 95))


def test_decode_tokens_give_each_later_token_its_context():
    r = driver.Rec(Req(0, np.zeros(10, np.int32), 3), due=0.0)
    r.token_times = [1.0, 2.0, 3.0]
    w = driver.Window(0.0, 10.0, [r], [], 10.0)
    assert list(stats.decode_tokens(w, 0.0, 10.0)) == [(10, 1), (10, 2)]
    assert list(stats.decode_tokens(w, 2.5, 10.0)) == [(10, 2)]


def test_closed_loop_ramp_spreads_the_first_sends():
    # the clients start one by one over the pre-roll before the window; what
    # they send then is not the window's, but its tokens inside it count
    pool = [Req(i, np.zeros(4, np.int32), 2) for i in range(200)]
    clock = Clock()
    eng = FakeEngine(clock, tick=1 / 64, prefill_ticks=1)
    d = driver.Driver(eng.submit, eng.step, clock=clock, sleep=clock.sleep)
    w = d.run(seconds=1.0, drain_s=5.0, pool=pool, clients=4, preroll_s=0.5)
    firsts = sorted(r.due - w.t0 for r in w.pre if r.req.index < 4)
    assert firsts == [-0.5, -0.375, -0.25, -0.125]
    assert w.pre and all(r.due < w.t0 for r in w.pre)
    assert w.recs and all(r.due >= w.t0 for r in w.recs)
    assert all(r.lag_s >= 0 for r in w.pre + w.recs)
    assert all(t.label == "preroll" for t in w.ticks if t.start < w.t0)
    # four clients, one token a tick each: the window's tokens include
    # those of pre-roll requests still running at its start
    n = stats.tokens_between(w, w.t0, w.t1)
    assert n > sum(1 for r in w.recs for t in r.token_times if t < w.t1)
    assert n == pytest.approx(4 * 64, rel=0.05)
