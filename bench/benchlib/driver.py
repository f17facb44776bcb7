"""Drives the engine through one window of a traffic mix and records, by
the host clock, what a user would see: when each request was due, when it
was sent, and when each of its tokens came back.

The engine keeps no per-token times, so the driver stamps each output token
at the return of the ``step()`` that delivered it (it watches
``len(request.generated)``).  One thread does everything: while ``step()``
runs, nothing is sent, and the lag of each send behind its due time is
recorded (``lag_s``).

Open loop: every request of the schedule is due inside the window and is
sent at its due time or as soon after as the loop gets to it.  Closed loop:
the clients start one by one, evenly spread over a pre-roll of
``preroll_s`` seconds before the window, so that the window opens on a
loop that has run and whose clients are out of step; each client sends
again the moment its previous request finishes, until the window closes,
and the due time is that moment.  A request sent in the pre-roll is not the
window's: only the tokens it delivers inside the window count.
After the window, the engine runs until every window request is done, or
for ``drain_s`` at most; a window request that has not delivered its
``max_new`` tokens by then counts as failed.  A pre-roll request still
running then is left unfinished: nothing reads it.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

from benchlib.traffic import Req


@dataclass
class Rec:
    """One request of the window as the client saw it (host clock, s)."""

    req: Req
    due: float
    sent: float = 0.0
    token_times: List[float] = field(default_factory=list)
    handle: object = None  # the engine's request object

    @property
    def done(self) -> bool:
        return len(self.token_times) >= self.req.max_new

    @property
    def lag_s(self) -> float:
        return self.sent - self.due


@dataclass
class Tick:
    start: float
    end: float
    label: str  # "step" inside the window, "drain" after it


@dataclass
class Window:
    """What one window recorded."""

    t0: float
    t1: float
    recs: List[Rec]  # the window's requests, in the order sent
    ticks: List[Tick]
    drained_at: float
    pre: List[Rec] = field(default_factory=list)  # sent in the pre-roll
    counters_start: Dict = field(default_factory=dict)
    counters_end: Dict = field(default_factory=dict)
    compiles: int = 0  # XLA compilations between t0 and the end of drain


class Driver:
    """One window of traffic against one engine.

    ``submit(req) -> handle`` and ``step()`` reach the system; ``annotate``
    gives a context manager that names a span in the profiler's trace (or
    does nothing)."""

    def __init__(self, submit: Callable, step: Callable, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 annotate: Optional[Callable[[str], object]] = None):
        self._submit = submit
        self._step = step
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate or (lambda name: nullcontext())

    def _send(self, rec: Rec, live: List[Rec]):
        rec.handle = self._submit(rec.req)
        rec.sent = self.clock()
        live.append(rec)

    def run(self, *, seconds: float, drain_s: float,
            schedule: Optional[List[Req]] = None,
            pool: Optional[List[Req]] = None, clients: int = 0,
            preroll_s: float = 0.0,
            on_tick: Optional[Callable[["Driver", float], None]] = None
            ) -> Window:
        """Open loop with ``schedule`` (each ``due_s`` an offset from the
        window's start), or closed loop with ``clients`` drawing from
        ``pool`` in order, client ``i`` sending first ``preroll_s * (1 -
        i / clients)`` seconds before the window opens.  ``on_tick(driver,
        now)`` runs between ticks, with ``driver.t0`` and ``driver.t_end``
        set (the harness starts and stops the trace there)."""
        clock = self.clock
        recs: List[Rec] = []
        pre: List[Rec] = []
        live: List[Rec] = []
        ticks: List[Tick] = []
        closed = schedule is None
        t0 = clock() + (preroll_s if closed else 0.0)
        t_end = t0 + seconds
        self.t0, self.t_end = t0, t_end
        pending: Deque[Rec] = deque()
        pool_iter = iter(pool or [])
        if closed:
            first = [next(pool_iter) for _ in range(clients)]
            pending.extend(Rec(r, t0 - preroll_s * (1 - i / max(clients, 1)))
                           for i, r in enumerate(first))
        else:
            pending.extend(Rec(r, t0 + r.due_s) for r in schedule)

        def send(rec: Rec):
            (recs if rec.due >= t0 else pre).append(rec)
            self._send(rec, live)

        while True:
            now = clock()
            if on_tick is not None:
                on_tick(self, now)
            if pending and pending[0].due <= now:
                with self.annotate("submit"):
                    while pending and pending[0].due <= now:
                        send(pending.popleft())
            if live:
                label = "step" if t0 <= now < t_end else (
                    "preroll" if now < t0 else "drain")
                with self.annotate(label):
                    self._step()
                t = clock()
                ticks.append(Tick(now, t, label))
                for rec in list(live):
                    n = len(rec.handle.generated)
                    if n > len(rec.token_times):
                        rec.token_times.extend([t] * (n - len(rec.token_times)))
                    if rec.handle.done or rec.done:
                        live.remove(rec)
                        if closed and t < t_end:
                            with self.annotate("submit"):
                                send(Rec(next(pool_iter), t))
                if t > t_end + drain_s or (t >= t_end and all(r.done for r in recs)):
                    break
            elif pending:
                wait = pending[0].due - clock()
                if wait > 0:
                    with self.annotate("wait"):
                        self.sleep(wait)
            else:
                break
        return Window(t0, t_end, recs, ticks, clock(), pre=pre)
