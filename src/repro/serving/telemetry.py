"""Spans, step records and request records of one serving engine.

Each `ContinuousBatchingEngine` owns one `Recorder` as `engine.telemetry`.
Recording is always on; what it keeps is bounded by fixed-size rings.

Spans. `serve.step` wraps one `engine.step()`; its children, in the order a
step runs them, are `serve.admit` (scheduler admission), `serve.plan` (lane
choice, page growth and preemption), `serve.draft` (speculative drafting),
`serve.inputs` (host arrays and their transfer), `serve.launch` (the jitted
call returning), `serve.wait` (reading the sampled tokens back, which
blocks on the device) and `serve.commit` (tokens to requests, stop
policies, completion and prefix promotion). A span is (sid, name, t0, t1,
parent sid, step number) on `time.perf_counter`. Every span is also a
`jax.profiler.TraceAnnotation`, so under a profiler it lands on the
trace's host timeline beside the device's ops; without one it costs an
idle enter and exit.

Step records. One per `serve.step`: its kind (mixed, decode, verify or
idle), the lanes it ran as (slot, q_start, n_new), the real tokens it fed
against the token slots of the program's shape, the rids that got a
token, and the active lanes, waiting requests and live pool pages after
it.

Request records. The engine's `Request` objects carry their own times
(`t_submit`, `t_admit` stamped in `PagedScheduler.admit`, `t_first` and
`t_done` at the end of the `serve.wait` whose sample gave that token) and
counts; the recorder keeps a reference to each, live ones until they
finish and finished ones in a ring, and `request(rid)` returns a frozen
view. Counters the engine and scheduler keep (`steps_run`,
`mixed_steps`, `sched.n_evictions`, ...) stay where they are counted.

`current()` returns the recorder of the newest engine still alive, for a
reader that holds no engine (the benchmark's metric readers); each engine
sees only its own records.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

clock = time.perf_counter

# Ring sizes: a step records up to 8 spans; 2048 steps hold about 250 s of
# 16 lanes of the 1B model at 0.12-0.33 s a step, with room to spare.
STEP_RING = 2048
SPAN_RING = 8 * STEP_RING
REQUEST_RING = 1024


class Span(NamedTuple):
    sid: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]            # sid of the enclosing span
    step: int                        # number of the step it belongs to


class StepRecord(NamedTuple):
    step: int
    kind: str                        # mixed, decode, verify or idle
    lanes: Tuple[Tuple[int, int, int], ...]   # (slot, q_start, n_new)
    tokens: int                      # real tokens the program was fed
    token_slots: int                 # tokens its shape holds
    emitted: Tuple[int, ...]         # rids that got a token
    active: int                      # after the step: lanes in use,
    waiting: int                     # requests queued,
    pages_live: int                  # and pool pages held


@dataclasses.dataclass(frozen=True)
class RequestView:
    """One request as `engine.request(rid)` reads it out."""
    rid: int
    mode: str
    prompt: Tuple[int, ...]          # as served, the mode's directive in
    out: Tuple[int, ...]
    t_submit: float
    t_admit: Optional[float]         # first admission
    t_first: Optional[float]
    t_done: Optional[float]
    n_admits: int
    n_preempted: int
    prefill_steps: int               # steps that wrote part of its prompt


_RECORDERS: List[weakref.ref] = []


def current() -> Optional["Recorder"]:
    """The recorder of the newest engine still alive, or None."""
    while _RECORDERS:
        rec = _RECORDERS[-1]()
        if rec is not None:
            return rec
        _RECORDERS.pop()
    return None


class _Span:
    __slots__ = ("_rec", "name", "sid", "parent", "t0", "t1", "_ann")

    def __init__(self, rec: "Recorder", name: str, parent: Optional[int]):
        self._rec, self.name, self.parent = rec, name, parent

    def __enter__(self) -> "_Span":
        self.sid = self._rec._new_sid()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock()
        self._ann.__exit__(*exc)
        self._rec.spans.append(Span(self.sid, self.name, self.t0, self.t1,
                                    self.parent, self._rec.n_steps - 1))


class _Step(_Span):
    """`serve.step`: the engine fills in what ran (`ran`, `emitted`)."""
    __slots__ = ("kind", "lanes", "tokens", "token_slots", "emitted")

    def __init__(self, rec: "Recorder"):
        super().__init__(rec, "serve.step", None)
        self.kind, self.lanes, self.emitted = "idle", [], []
        self.tokens = self.token_slots = 0

    def ran(self, kind: str, lanes, token_slots: int) -> None:
        """The program this step ran, its working lanes as (slot,
        q_start, n_new), and the tokens its shape holds."""
        self.kind = kind
        self.lanes += lanes
        self.tokens += sum(n for _, _, n in lanes)
        self.token_slots += token_slots

    def __enter__(self) -> "_Step":
        self._rec.n_steps += 1
        self._rec._open = self
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        rec = self._rec
        rec._open = None
        sched = rec._sched
        rec.steps.append(StepRecord(
            rec.n_steps - 1, self.kind, tuple(self.lanes), self.tokens,
            self.token_slots, tuple(self.emitted), len(sched.active),
            len(sched.waiting), sched.alloc.n_live))


class Recorder:
    """What one engine recorded; see the module docstring."""

    def __init__(self, sched):
        self._sched = sched
        self.spans: deque = deque(maxlen=SPAN_RING)
        self.steps: deque = deque(maxlen=STEP_RING)
        self.n_steps = 0
        self._sid = 0
        self._open: Optional[_Step] = None
        self._live: Dict[int, object] = {}
        self._done: "OrderedDict[int, object]" = OrderedDict()
        _RECORDERS.append(weakref.ref(self))

    def _new_sid(self) -> int:
        self._sid += 1
        return self._sid

    # -- spans ---------------------------------------------------------------

    def step(self) -> _Step:
        """The `serve.step` span around one engine step."""
        return _Step(self)

    def span(self, name: str) -> _Span:
        """A child of the open `serve.step` (a root span outside one)."""
        return _Span(self, name,
                     None if self._open is None else self._open.sid)

    def step_times(self) -> List[Tuple[StepRecord, Span, float]]:
        """Each step still in both rings, with its `serve.step` span and
        the seconds its `serve.wait` children took."""
        step_span, wait = {}, {}
        for s in self.spans:
            if s.name == "serve.step":
                step_span[s.step] = s
            elif s.name == "serve.wait":
                wait[s.step] = wait.get(s.step, 0.0) + s.t1 - s.t0
        return [(r, step_span[r.step], wait.get(r.step, 0.0))
                for r in self.steps if r.step in step_span]

    # -- requests ------------------------------------------------------------

    def track(self, req) -> None:
        """A request just submitted (an engine `Request`, by reference)."""
        self._live[req.rid] = req

    def finish(self, req) -> None:
        self._live.pop(req.rid, None)
        self._done[req.rid] = req
        if len(self._done) > REQUEST_RING:
            self._done.popitem(last=False)

    def request(self, rid: int) -> RequestView:
        """A frozen view of a live request or a recently finished one
        (KeyError once it has left the ring)."""
        req = self._live.get(rid)
        if req is None:
            req = self._done[rid]
        return RequestView(
            rid=req.rid, mode=req.mode, prompt=tuple(req.prompt),
            out=tuple(req.out), t_submit=req.t_submit, t_admit=req.t_admit,
            t_first=req.t_first, t_done=req.t_done, n_admits=req.n_admits,
            n_preempted=req.preemptions, prefill_steps=req.prefill_steps)

    def requests(self) -> List[RequestView]:
        """Every request held, finished ones first, in submission order
        within each."""
        return [self.request(rid) for rid in [*self._done, *self._live]]

    # -- read-out --------------------------------------------------------------

    def summary(self) -> Dict[str, Optional[float]]:
        """Medians over the requests held of time to first token from
        submission and its two parts (admission wait, then prefill until
        the first token), and the host's mean milliseconds per working
        step (`serve.step` less its `serve.wait`)."""
        views = [v for v in self.requests() if v.t_first is not None]
        work = [(sp.t1 - sp.t0 - w) for r, sp, w in self.step_times()
                if r.kind != "idle"]

        def p50_ms(xs):
            return float(1e3 * np.median(xs)) if xs else None

        return {
            "requests": len(views),
            "ttft_p50_ms": p50_ms([v.t_first - v.t_submit for v in views]),
            "admit_wait_p50_ms": p50_ms([v.t_admit - v.t_submit
                                         for v in views]),
            "prefill_p50_ms": p50_ms([v.t_first - v.t_admit for v in views]),
            "steps": len(work),
            "host_ms_per_step": (float(1e3 * np.mean(work)) if work
                                 else None),
        }
