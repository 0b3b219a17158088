"""The measured window: a mix's requests into ``ScheduledEngine``, every
emitted token timed by the harness.

``ScheduledEngine.step`` returns after the decode step's tokens reach the
host, so a token's time is read right after the ``step`` that emitted
it.  Nothing here reads a span or counter of the program beyond the
request objects and ``last_schedule``.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.traffic import Arrival, Mix

@dataclass
class Tracked:
    req: object  # repro.serving.Request
    arrival: Arrival
    times: List[float] = field(default_factory=list)
    submitted: float = 0.0
    finished_at: Optional[float] = None


@dataclass
class StepRec:
    t0: float
    t1: float
    live: List[int]  # cache tokens each decoding slot attends this step
    chunks: List[Tuple[int, int, bool]]  # prompt [start, end), final


@dataclass
class Result:
    requests: List[Tracked]
    steps: List[StepRec]
    window_s: float
    seconds: float


class Runner:
    def __init__(self, eng, mix: Mix, make_request: Callable,
                 clock=time.perf_counter, span=None):
        self.eng, self.mix, self.make_request = eng, mix, make_request
        self.clock = clock
        self.span = span or (lambda name: contextlib.nullcontext())
        self.t0 = clock()
        self.live: Dict[int, Tracked] = {}
        self.all: List[Tracked] = []
        self.steps: List[StepRec] = []
        self.timers: List[Tuple[float, Callable]] = []

    def now(self) -> float:
        return self.clock() - self.t0

    def submit(self, a: Arrival) -> None:
        req = self.make_request(self.mix.tokens(a.prompt_len), a.output_len)
        tr = Tracked(req=req, arrival=a, submitted=self.now())
        self.eng.submit(req)
        self.live[id(req)] = tr
        self.all.append(tr)

    def step(self) -> List[Tracked]:
        """One engine iteration; returns the requests it finished."""
        eng = self.eng
        live = [len(r.prompt) + len(r.out_tokens)
                for r in eng.active.values()]
        t_s = self.now()
        with self.span("bench.step"):
            eng.step()
        t = self.now()
        chunks = [(cp.start, cp.end, cp.final)
                  for cp in eng.last_schedule.chunks]
        self.steps.append(StepRec(t_s, t, live, chunks))
        done = []
        for key, tr in list(self.live.items()):
            out = tr.req.out_tokens or []
            tr.times.extend([t] * (len(out) - len(tr.times)))
            if out and tr.req.slot == -1:
                tr.finished_at = t
                del self.live[key]
                done.append(tr)
        for at, fn in list(self.timers):
            if t >= at:
                self.timers.remove((at, fn))
                fn()
        return done

    # -- phases -----------------------------------------------------------
    def fill_closed(self) -> None:
        """Closed loop: admit every client's first request and prefill
        them all before the window opens."""
        with self.span("bench.admit"):
            for a in self.mix.initial:
                self.submit(a)
        eng = self.eng
        while eng.waiting or eng.prefilling or eng.preempted:
            for tr in self.step():
                self.replace(tr)
        self.rebase(self.now())

    def rebase(self, dt: float) -> None:
        """Move time zero ``dt`` seconds later: what was recorded so far
        lands before the window."""
        self.t0 += dt
        for s in self.steps:
            s.t0 -= dt
            s.t1 -= dt
        for x in self.all:
            x.times = [t - dt for t in x.times]
            x.submitted -= dt
            x.arrival.due -= dt
            if x.finished_at is not None:
                x.finished_at -= dt

    def replace(self, tr: Tracked) -> None:
        with self.span("bench.generator"):
            a = self.mix.next_for(tr.arrival.client, self.now())
        with self.span("bench.admit"):
            self.submit(a)

    def run_closed(self, seconds: float) -> float:
        while True:
            for tr in self.step():
                self.replace(tr)
            t = self.now()
            if t >= seconds:
                return t


# -- end-to-end numbers ------------------------------------------------------
def in_window(res: Result) -> List[Tracked]:
    return [x for x in res.requests if 0 <= x.arrival.due < res.seconds]


def tokens_in_window(res: Result) -> int:
    return sum(1 for x in res.requests for t in x.times
               if 0 <= t <= res.window_s)


def gaps_ms(res: Result) -> np.ndarray:
    g = [1e3 * (b - a) for x in res.requests
         for a, b in zip(x.times, x.times[1:])
         if a >= 0 and b <= res.window_s]
    return np.asarray(g, np.float64)
