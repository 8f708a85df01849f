"""Drives the program's serving loop (`submit`, `step`) and stamps times.

The batcher counts steps; the times here are the harness's own
`time.perf_counter()`: when each request was due, and the end of each
`step()` after which its output grew.
Host spans (`bench.submit`, `bench.step`, `bench.wait`, `bench.drain`) go
into the profiler's trace when one is being taken."""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from .traffic import Planned


@dataclasses.dataclass
class Record:
    rid: int
    prompt_len: int
    max_new: int
    due: float                # seconds on the harness clock
    first: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    finished_at: Optional[float] = None


@dataclasses.dataclass
class Step:
    start: float
    end: float
    active: int               # StepHealth.active: slots busy in the step
    reserved: float = 0.0     # share of the KV pool's pages reserved after it
    live: float = 0.0         # share of the pool's token rows holding a token


@dataclasses.dataclass
class Window:
    t0: float                 # the window opens at a step boundary after the warm-up
    t1: float                 # and closes at the first one >= t0 + seconds
    records: Dict[int, Record]
    steps: List[Step]         # every step, the warm-up's and drain's included
    drain_s: float = 0.0
    unanswered: List[int] = dataclasses.field(default_factory=list)
    queued: Tuple[int, int] = (0, 0)   # requests queued at t0 and at t1
    busy_at_open: int = 0              # slots busy at t0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records.values()
                if self.t0 <= r.due <= self.t1]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps
                if s.start >= self.t0 and s.end <= self.t1]

    def finished(self) -> List[Record]:
        """Requests that finished after the window opened (in it or in
        the drain)."""
        return [r for r in self.records.values()
                if r.finished_at is not None and r.finished_at >= self.t0]


class Driver:
    """One run of a traffic plan through ``batcher``.

    ``hook(step_index)`` runs before each step (the tracer starts and stops
    there, between steps); ``make_request(planned)`` builds the program's
    request object."""

    def __init__(self, batcher, make_request: Callable, clock=time.perf_counter):
        self.batcher = batcher
        self.make_request = make_request
        self.clock = clock
        self.records: Dict[int, Record] = {}
        self.live: Dict[int, object] = {}   # rid -> program request
        self.steps: List[Step] = []

    def _submit(self, p: Planned, due: float):
        req = self.make_request(p)
        self.records[p.rid] = Record(p.rid, len(p.prompt), p.max_new, due)
        self.live[p.rid] = req
        self.batcher.submit(req)

    def _step(self) -> List[int]:
        """One `step()`; stamps every request whose output grew and returns
        the rids that finished in it."""
        t_start = self.clock()
        with TraceAnnotation("bench.step"):
            self.batcher.step()
        t_end = self.clock()
        pool = self.batcher.pool_stats()
        self.steps.append(Step(
            t_start, t_end, self.batcher.health[-1].active,
            *((pool.utilization,
               pool.live_tokens / (pool.num_pages * pool.page_size))
              if pool else ())))
        done = []
        for rid, req in list(self.live.items()):
            rec = self.records[rid]
            grown = len(req.output) - len(rec.times)
            if grown > 0:
                rec.times.extend([t_end] * grown)
                if rec.first is None:
                    rec.first = t_end
            if req.finish_reason is not None:
                rec.finish_reason = req.finish_reason
                rec.finished_at = t_end
                del self.live[rid]
                done.append(rid)
        return done

    def _busy(self) -> bool:
        return bool(self.batcher.queue) or self.batcher.active > 0

    def run(self, plan: List[Planned], seconds: float, warm_s: float = 0.0,
            hook: Optional[Callable[[int], None]] = None,
            at_open: Optional[Callable[[float], None]] = None,
            drain_cap_s: float = 120.0) -> Window:
        """Submits each planned request when it falls due (``due_s`` after
        the start) and steps while any is queued or live.  The first
        ``warm_s`` seconds are untimed: they fill the batcher to the
        mix's steady load.  The window opens at the first step boundary
        past them (``at_open(t0)`` runs there) and closes at the first one
        ``seconds`` later."""
        hook = hook or (lambda i: None)
        at_open = at_open or (lambda t: None)
        start = self.clock()
        t0 = end = None
        queued_open = busy_open = 0
        pending = deque(sorted(plan, key=lambda p: p.due_s))
        while True:
            now = self.clock()
            if t0 is None and now >= start + warm_s:
                t0, end = now, now + seconds
                queued_open = len(self.batcher.queue)
                busy_open = self.batcher.active
                at_open(t0)
            if end is not None and now >= end:
                break
            with TraceAnnotation("bench.submit"):
                while pending and start + pending[0].due_s <= now:
                    p = pending.popleft()
                    self._submit(p, start + p.due_s)
            if self._busy():
                hook(len(self.steps))
                self._step()
            else:
                nxt = end if end is not None else start + warm_s
                if pending:
                    nxt = min(nxt, start + pending[0].due_s)
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, nxt - self.clock()))
        t1 = now
        hook(len(self.steps))
        win = Window(t0, t1, self.records, self.steps,
                     queued=(queued_open, len(self.batcher.queue)),
                     busy_at_open=busy_open)

        # drain: no new arrivals; step until every request due in the
        # window has its first token and some request has finished in it
        # (the check samples those), or the cap runs out
        def outstanding():
            return (any(r.first is None for r in win.due_in_window())
                    or not win.finished())

        with TraceAnnotation("bench.drain"):
            while outstanding() and self.clock() < t1 + drain_cap_s \
                    and self._busy():
                self._step()
        win.drain_s = self.clock() - t1
        win.unanswered = sorted(r.rid for r in win.due_in_window()
                                if r.first is None)
        return win
