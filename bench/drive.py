"""The open-loop and closed-loop drivers.  They feed the engine through
``submit`` / ``step`` on the benchmark's own clock, never ``engine.run``
(which skips idle time and has no window), and stamp each request's
tokens at the moment the step that made them returns.

Times are seconds on the window's clock: 0 is when the window opens.
Each call into the engine, and each wait for the next arrival, runs under
a ``jax.profiler.TraceAnnotation`` (``bench.step``, ``bench.submit``,
``bench.wait``), so that a traced window can say what the host was doing
in each of the device's idle gaps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

# an open-loop request still without its first token this long after the
# window closed counts as failed
DRAIN_LIMIT_S = 60.0


@dataclass
class Track:
    """What the driver saw of one request."""
    planned: object                 # traffic.generate.Planned
    req: object = None              # the program's Request
    due: float = 0.0                # due (open loop) or sent (closed loop)
    sent: float | None = None       # when the driver submitted it
    stamps: list = field(default_factory=list)   # one time per token
    done: bool = False

    @property
    def first(self):
        return self.stamps[0] if self.stamps else None


class Window:
    """Clock, step loop and token stamping shared by both drivers.
    ``on_step(window, t, finished)`` is called after every step with the
    window time and the requests that finished in it."""

    def __init__(self, engine, make_request, on_step=None):
        self.engine = engine
        self.make_request = make_request
        self.on_step = on_step
        self.t0 = time.perf_counter()
        self.live: list[Track] = []
        self.tracks: list[Track] = []
        self.steps = 0
        self.step_s: list[tuple[float, float]] = []   # (end, seconds)

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def send(self, tr: Track, t: float) -> None:
        tr.req = self.make_request(tr.planned, tr.due)
        tr.sent = t
        with TraceAnnotation("bench.submit"):
            self.engine.submit(tr.req)
        self.live.append(tr)
        self.tracks.append(tr)

    def step(self) -> list[Track]:
        """One engine step; stamps new tokens; returns the requests that
        finished in it."""
        t_in = self.now()
        with TraceAnnotation("bench.step"):
            self.engine.step()
        t = self.now()
        self.steps += 1
        self.step_s.append((t, t - t_in))
        finished = []
        for tr in self.live:
            n = len(tr.req.generated)
            if n > len(tr.stamps):
                tr.stamps.extend([t] * (n - len(tr.stamps)))
            if tr.req.state.value == "done":
                tr.done = True
                finished.append(tr)
        if finished:
            self.live = [tr for tr in self.live if not tr.done]
        if self.on_step is not None:
            self.on_step(self, t, finished)
        return finished

    @property
    def busy(self) -> bool:
        return not self.engine.sched.idle


def open_loop(engine, planned, seconds, make_request, on_step=None,
              on_open=None):
    """Send each request when it is due, step while there is work, sleep
    to the next due time when there is none.  Requests due before 0 (the
    mix's pre-roll) start the clock at the first due time, so the engine
    carries its steady load when the window opens at 0; ``on_open(window)``
    is called then.  After ``seconds`` nothing more is sent; the engine
    keeps stepping until every request has its first token (at most
    ``DRAIN_LIMIT_S`` more).  Returns (window, tracks)."""
    w = Window(engine, make_request, on_step)
    if planned:
        w.t0 -= min(0.0, planned[0].due)
    pending = [Track(planned=p, due=p.due) for p in planned]
    opened = False
    i = 0
    while True:
        t = w.now()
        if not opened and t >= 0.0:
            opened = True
            if on_open is not None:
                on_open(w)
        while i < len(pending) and pending[i].due <= t:
            w.send(pending[i], t)
            i += 1
        if t >= seconds and i == len(pending):
            if all(tr.first is not None for tr in w.tracks) \
                    or t >= seconds + DRAIN_LIMIT_S:
                break
        if w.busy:
            w.step()
        elif i < len(pending):
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, pending[i].due - w.now()))
        else:
            break
    return w, pending


def closed_loop(engine, queues, seconds, make_request, on_step=None,
                on_open=None):
    """Each client sends its next request as soon as the last completes.
    The clients' first requests are sent and prefilled before the window
    opens (their first tokens are made before 0), so the window measures
    steady decoding.  ``on_open(window)`` is called as the window opens.
    Returns (window, tracks of requests sent)."""
    w = Window(engine, make_request, on_step)
    nxt = [0] * len(queues)

    def send_next(c, t):
        if nxt[c] < len(queues[c]):
            tr = Track(planned=queues[c][nxt[c]], due=t)
            nxt[c] += 1
            w.send(tr, t)

    for c in range(len(queues)):
        send_next(c, w.now())
    while any(tr.first is None for tr in w.live) and w.busy:
        w.step()
    # the window opens now
    shift = w.now()
    w.t0 += shift
    for tr in w.tracks:
        tr.due -= shift
        tr.sent -= shift
        tr.stamps = [s - shift for s in tr.stamps]
    if on_open is not None:
        on_open(w)
    while w.now() < seconds and w.busy:
        for tr in w.step():
            send_next(tr.planned.client, w.now())
    return w, w.tracks
