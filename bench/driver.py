"""The benchmark's load driver: steps a serving engine under a traffic
mix and stamps every request on the host clock.

The engine is driven through its public protocol (``add``,
``can_accept``, ``step``) and read through the requests it was handed
(``out_tokens``, ``done``, ``truncated``) and its scheduler's queue.
Stamps are taken when ``step()`` returns, so a token's time is the end
of the step that produced it.

- Open loop: a request is due at its scheduled arrival, and its latency
  runs from then, whether or not the engine could take it (backpressure
  and a late generator are charged to the request).
- Closed loop: each client sends its next request when its last one
  finishes; the request is due when it is sent. Every client sends its
  first request at the start.

The window opens ``warm_s`` after the start. Requests due in the window
are its sample. After the window no request is sent. An open loop then steps the engine until every request of the
sample has its first token, or until ``drain_s`` has passed; a closed
loop stops at the close, its clients' requests in flight.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Record:
    """One request as the load driver saw it (host-clock seconds)."""

    req: object                 # the engine's Request
    due: float                  # when it was due (open) or sent (closed)
    in_window: bool             # due inside the measured window
    added: float = 0.0          # when the engine took it
    admitted: float = 0.0       # start of the step that gave it a slot
    stamps: List[float] = dataclasses.field(default_factory=list)
    client: int = -1


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    drained: float = 0.0        # end of the drain after the window
    steps: List[tuple] = dataclasses.field(default_factory=list)  # (start, end)
    # after each step of the window: (slots holding a request, share of
    # the cache's pages held)
    load: List[tuple] = dataclasses.field(default_factory=list)


class Driver:
    """Steps ``engine`` under ``mix`` (see bench/traffic.py)."""

    def __init__(self, engine, make_request: Callable, loop: str, *,
                 clients: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 spans: bool = False,
                 on_window: Optional[Callable[[bool], None]] = None,
                 marks: Optional[list] = None):
        self.eng = engine
        self.make_request = make_request
        self.loop = loop
        self.clients = clients
        self.clock = clock
        # host spans for the profiler's trace, when one is taken
        self.span = (TraceAnnotation if spans
                     else lambda name: contextlib.nullcontext())
        self.on_window = on_window or (lambda opening: None)
        # (seconds into the window, callback): each called once, between
        # steps, when the window has run that long
        self.marks = sorted(marks or [], key=lambda m: m[0])
        self.records: List[Record] = []
        self._open: List[Record] = []      # added, not done
        self._queued: List[Record] = []    # added, not yet in a slot

    # ------------------------------------------------------------------
    def _add(self, rec: Record, now: float) -> None:
        rec.added = now
        if not self.eng.add(rec.req):
            raise RuntimeError("engine refused a request it said it could take")
        self.records.append(rec)
        self._open.append(rec)
        self._queued.append(rec)

    def _step(self, win: Optional[Window]) -> bool:
        start = self.clock()
        with self.span("bench.step"):
            did = self.eng.step()
        now = self.clock()
        if did and win is not None:
            win.steps.append((start, now))
            sched = self.eng.sched
            win.load.append((sum(s.req is not None for s in sched.slots),
                             sched.kv.occupancy()))
        if self._queued:
            waiting = {id(r) for r in self.eng.sched.queue}
            still = []
            for rec in self._queued:
                if id(rec.req) in waiting:
                    still.append(rec)
                else:
                    rec.admitted = start
            self._queued = still
        live = []
        for rec in self._open:
            n = len(rec.req.out_tokens)
            while len(rec.stamps) < n:
                rec.stamps.append(now)
            if not rec.req.done:
                live.append(rec)
        self._open = live
        return did

    def _tick(self, now: float, win: Window) -> None:
        while self.marks and now >= win.t0 + self.marks[0][0]:
            self.marks.pop(0)[1]()

    def _idle(self, until: float) -> None:
        with self.span("bench.wait"):
            time.sleep(max(0.0, min(0.001, until - self.clock())))

    # ------------------------------------------------------------------
    def run(self, warm: list, window: list, warm_s: float, window_s: float,
            drain_s: float) -> Window:
        """Run the warm-up requests, then the window's, then drain."""
        if self.loop == "open":
            return self._run_open(warm, window, warm_s, window_s, drain_s)
        return self._run_closed(warm + window, warm_s, window_s)

    def _run_open(self, warm, window, warm_s, window_s, drain_s) -> Window:
        start = self.clock()
        due, t = [], start
        for r in warm:
            t += r.gap_s
            due.append(t)
        t0 = t = start + warm_s
        for r in window:
            t += r.gap_s
            due.append(t)
        todo = list(zip(due, [False] * len(warm) + [True] * len(window),
                        warm + window))
        win = Window(t0=t0, t1=t0 + window_s)
        i = 0
        opened = False
        while True:
            now = self.clock()
            if not opened and now >= win.t0:
                self.on_window(True)
                opened = True
            if opened:
                self._tick(now, win)
            with self.span("bench.release"):
                # at the close, what fell due before it is still sent
                while (i < len(todo) and todo[i][0] <= min(now, win.t1)
                       and self.eng.can_accept()):
                    d, inside, r = todo[i]
                    self._add(Record(self.make_request(r), d, inside), now)
                    i += 1
            if now >= win.t1:
                self.on_window(False)
                break
            in_win = now >= win.t0
            if not self._step(win if in_win else None):
                nxt = todo[i][0] if i < len(todo) else win.t1
                self._idle(min(nxt, win.t1))
        if i < len(todo):
            # due in the window and never released: the engine's queue
            # was full to the end; each counts as failed
            for d, inside, r in todo[i:]:
                if inside and d < win.t1:
                    self.records.append(Record(self.make_request(r), d, True))
        self._drain(win, drain_s)
        return win

    def _run_closed(self, reqs, warm_s, window_s) -> Window:
        start = self.clock()
        win = Window(t0=start + warm_s, t1=start + warm_s + window_s)
        # when each client sends next; None while its request is out
        send_at: List[Optional[float]] = [start] * self.clients
        out: List[Optional[Record]] = [None] * self.clients
        nxt = 0
        opened = False
        while True:
            now = self.clock()
            if not opened and now >= win.t0:
                self.on_window(True)
                opened = True
            if opened:
                self._tick(now, win)
            if now >= win.t1:
                self.on_window(False)
                break
            with self.span("bench.release"):
                for c in range(self.clients):
                    if out[c] is not None and out[c].req.done:
                        out[c], send_at[c] = None, now
                    if send_at[c] is None or now < send_at[c]:
                        continue
                    if nxt >= len(reqs):
                        raise RuntimeError(
                            f"the closed loop used all {len(reqs)} requests "
                            "of its mix before the window closed; raise "
                            "window_requests")
                    out[c] = Record(self.make_request(reqs[nxt]), now,
                                    now >= win.t0, client=c)
                    send_at[c] = None
                    nxt += 1
                    self._add(out[c], now)
            if not self._step(win if now >= win.t0 else None):
                pending = [t for t in send_at if t is not None]
                self._idle(min(pending + [win.t1]))
        win.drained = self.clock()
        return win

    def _drain(self, win: Window, drain_s: float) -> None:
        deadline = self.clock() + drain_s
        waiting = [r for r in self.records if r.in_window]
        while self.clock() < deadline:
            if all(r.stamps for r in waiting if r.added):
                break
            if not self._step(None):
                break
        win.drained = self.clock()


# ---------------------------------------------------------------------------
# Reductions of the records (host clock)
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))


def ttfts(records: List[Record], win: Window) -> List[float]:
    """First-token latency of every request due in the window, from when
    it was due; one that never got a token counts to the drain's end."""
    out = []
    for r in records:
        if r.in_window:
            first = r.stamps[0] if r.stamps else win.drained
            out.append(first - r.due)
    return out


def token_gaps(records: List[Record], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens of a request, for every gap whose
    later token came inside [t0, t1)."""
    out = []
    for r in records:
        s = r.stamps
        for a, b in zip(s, s[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def tokens_in(records: List[Record], t0: float, t1: float) -> int:
    return sum(1 for r in records for s in r.stamps if t0 <= s < t1)


def failed(records: List[Record], loop: str) -> List[Record]:
    """Requests of the window that the engine cut short for want of
    cache, and in an open loop those with no first token by the end of
    the drain (a closed loop's requests in flight at the close are
    neither finished nor failed)."""
    return [r for r in records if r.in_window and (
        r.req.truncated or (loop == "open" and not r.stamps))]
