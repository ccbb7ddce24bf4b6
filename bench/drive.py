"""Drive a traffic mix through the server and record what happened: one
record per request (due, submit, admission, the time each new token was
first seen) and one per engine step (host clock around it, its kind, its
working lanes). Host spans (`engine.step`, `traffic.submit`,
`bench.observe`, `bench.idle`) go into the profiler's trace when one is
taken, so that idle gaps on the device can be put down to what the host
was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax

from bench.traffic import Request

clock = time.perf_counter


@dataclasses.dataclass
class RequestRecord:
    rid: int
    due: float
    submit: float
    n_out: int
    prompt_len: int
    admit: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    kind: str
    lanes: list
    emitted: int
    traced: bool
    held: int = 0                     # tokens in the active lanes' caches


@dataclasses.dataclass
class Record:
    window: tuple                     # (start, end) on the host clock
    stop: float = 0.0                 # when the loop stopped
    requests: List[RequestRecord] = dataclasses.field(default_factory=list)
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    stalls: List[tuple] = dataclasses.field(default_factory=list)

    def window_steps(self, kind: Optional[str] = None) -> List[StepRecord]:
        lo, hi = self.window
        return [s for s in self.steps if lo <= s.t0 and s.t1 <= hi
                and (kind is None or s.kind == kind)]

    @property
    def held_tokens(self) -> int:
        """The most tokens the lanes held after any step of the window."""
        return max((s.held for s in self.window_steps()), default=0)

    def due_in_window(self) -> List[RequestRecord]:
        lo, hi = self.window
        return [r for r in self.requests if lo <= r.due < hi]

    def held_by_profiler(self, r: RequestRecord) -> bool:
        """Whether the request waited across the profiler's start or stop,
        which block the loop (traced runs only)."""
        return any(r.due < b and r.submit > a for a, b in self.stalls)


class Tracer:
    """Takes one profiler trace of about `seconds` from `start`, so that it
    holds both step programs: it starts at the first step boundary after
    `start` that follows a decode step (or `seconds` later whatever came
    before), and stops at the first boundary once `seconds` have passed
    and it has seen a mixed and a decode step, or at `last` at the latest."""

    KINDS = {"mixed", "decode"}

    def __init__(self, directory: str, start: float, seconds: float,
                 last: float):
        self.dir, self.start, self.seconds, self.last = (directory, start,
                                                         seconds, last)
        self.on = self.done = False
        self.t_on, self.prev, self.seen = 0.0, None, set()
        self.stalls: List[tuple] = []     # host intervals start/stop blocked

    def observe(self, kind: Optional[str]) -> None:
        """The kind of the step that just ran."""
        self.prev = kind
        if self.on and kind is not None:
            self.seen.add(kind)

    def at_boundary(self, now: float) -> None:
        if (not self.done and not self.on and now >= self.start
                and (self.prev == "decode"
                     or now >= self.start + self.seconds)):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans only, no py calls
            opts.enable_hlo_proto = False
            a = clock()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.stalls.append((a, clock()))
            self.on, self.t_on = True, now
        elif self.on and (now >= self.last
                          or (now >= self.t_on + self.seconds
                              and self.seen >= self.KINDS)):
            self.close()

    def close(self) -> None:
        if self.on:
            a = clock()
            jax.profiler.stop_trace()
            self.stalls.append((a, clock()))
            self.on, self.done = False, True


class Loop:
    """The shared part of both loops: submitting, stepping, observing."""

    def __init__(self, server, mode: str, tracer: Optional[Tracer]):
        self.server, self.mode, self.tracer = server, mode, tracer
        self.live: Dict[int, RequestRecord] = {}
        self.all: List[RequestRecord] = []
        self.steps: List[StepRecord] = []
        self.finished: List[RequestRecord] = []

    def submit(self, req: Request, due: float) -> RequestRecord:
        with jax.profiler.TraceAnnotation("traffic.submit"):
            rid = self.server.submit(req.prompt, req.n_out, self.mode)
        rec = RequestRecord(rid, due, clock(), req.n_out, len(req.prompt))
        self.live[rid] = rec
        self.all.append(rec)
        return rec

    def busy(self) -> bool:
        return bool(self.live)

    def step(self) -> None:
        if self.tracer is not None:
            self.tracer.at_boundary(clock())
        t0 = clock()
        with jax.profiler.TraceAnnotation("engine.step"):
            info = self.server.step()
        t1 = clock()
        with jax.profiler.TraceAnnotation("bench.observe"):
            traced = self.tracer is not None and self.tracer.on
            if self.tracer is not None:
                self.tracer.observe(info.kind)
            if info.kind is not None:
                self.steps.append(StepRecord(t0, t1, info.kind, info.lanes,
                                             len(info.emitted), traced,
                                             self.server.held_tokens()))
            for rid in info.emitted:
                rec = self.live[rid]
                n = len(self.server.out(rid))
                while len(rec.tokens) < n:
                    rec.tokens.append(t1)
                if n >= rec.n_out:
                    rec.done = t1
                    self.finished.append(self.live.pop(rid))
            for rid in self.server.active_rids():
                rec = self.live.get(rid)
                if rec is not None and rec.admit is None:
                    rec.admit = t1

    def idle_until(self, t: float) -> None:
        with jax.profiler.TraceAnnotation("bench.idle"):
            while clock() < t:
                time.sleep(min(0.001, max(0.0, t - clock())))


def warm_shapes(server, mode: str, chunk: int) -> None:
    """Run one request through the mixed and the decode program (and the
    sampler), so that neither compiles inside the window."""
    loop = Loop(server, mode, None)
    loop.submit(Request(0.0, list(range(chunk + 7)), 3), clock())
    while loop.busy():
        loop.step()


def open_loop(server, reqs: List[Request], mode: str, warm_s: float,
              seconds: float, drain_s: float, tracer_for: Callable,
              on_window: Callable[[], None]) -> Record:
    """Arrivals on the schedule whatever the server does. The window starts
    `warm_s` after the schedule; after it closes the loop keeps serving
    (arrivals too) until every request due in the window has its first
    token, for at most `drain_s`."""
    t_sched = clock()
    w0, w1 = t_sched + warm_s, t_sched + warm_s + seconds
    tracer = tracer_for(w0, w1)
    loop = Loop(server, mode, tracer)
    i, started = 0, False
    while True:
        now = clock()
        if not started and now >= w0:
            on_window()
            started = True
        while i < len(reqs) and t_sched + reqs[i].due <= now:
            loop.submit(reqs[i], t_sched + reqs[i].due)
            i += 1
        if now >= w1:
            waiting = [r for r in loop.all
                       if w0 <= r.due < w1 and not r.tokens]
            if not waiting or now >= w1 + drain_s:
                break
        if loop.busy():
            loop.step()
        else:
            nxt = t_sched + reqs[i].due if i < len(reqs) else now + 1e-3
            for mark in (w0, w1):
                if now < mark:
                    nxt = min(nxt, mark)
            loop.idle_until(nxt)
    if tracer is not None:
        tracer.close()
    return Record((w0, w1), clock(), loop.all, loop.steps,
                  stalls=tracer.stalls if tracer is not None else [])


def closed_loop(server, reqs: List[Request], sessions: int, mode: str,
                seconds: float, tracer_for: Callable,
                on_window: Callable[[], None]) -> Record:
    """`sessions` clients; the first requests' caches (staggered through
    their outputs) are built in set-up, and the window starts once every
    client has its first token. A finished request is replaced at once."""
    loop = Loop(server, mode, None)
    for req in reqs[:sessions]:
        loop.submit(req, clock())
    nxt = sessions
    while any(not r.tokens for r in loop.all):
        loop.step()
    on_window()
    w0 = clock()
    w1 = w0 + seconds
    loop.tracer = tracer_for(w0, w1)
    while clock() < w1:
        n_done = len(loop.finished)
        loop.step()
        for _ in range(len(loop.finished) - n_done):
            if nxt < len(reqs):
                loop.submit(reqs[nxt], clock())
                nxt += 1
    if loop.tracer is not None:
        loop.tracer.close()
    return Record((w0, w1), clock(), loop.all, loop.steps,
                  stalls=loop.tracer.stalls if loop.tracer else [])
