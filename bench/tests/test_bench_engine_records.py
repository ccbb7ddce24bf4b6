"""CPU tests of the metric readers that read the serving engine's own
records (`bench/engine_records.py`): each on a recorder built by hand, None
on an empty recorder, and None where the program has no recorder."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.drive import Record, RequestRecord  # noqa: E402
from bench.run import Context, load_reader  # noqa: E402
from repro.serving import telemetry  # noqa: E402
from repro.serving.scheduler import Request  # noqa: E402

READERS = ("admit_wait_ms.p90", "prefill_ms.p50", "host_ms.step",
           "wait_ms.decode", "kv_pages_used.peak")


def _recorder():
    sched = SimpleNamespace(active={}, waiting=[],
                            alloc=SimpleNamespace(n_live=0))
    return telemetry.Recorder(sched)


def _step(rec, n, kind, t0, t1, wait, pages):
    """Step `n` from t0 to t1 whose serve.wait took `wait` seconds."""
    rec.spans.append(telemetry.Span(2 * n, "serve.step", t0, t1, None, n))
    rec.spans.append(telemetry.Span(2 * n + 1, "serve.wait", t1 - wait, t1,
                                    2 * n, n))
    rec.steps.append(telemetry.StepRecord(n, kind, ((0, 0, 1),), 1, 4, (),
                                          1, 0, pages))


def _ctx(requests=()):
    record = Record((10.0, 20.0), stop=25.0, requests=list(requests))
    return Context(record, {}, {}, {"n_pages": 200}, {}, 1.0)


@pytest.fixture
def hand_built():
    rec = _recorder()
    _step(rec, 0, "mixed", 9.0, 9.5, 0.3, 150)       # before the window
    _step(rec, 1, "mixed", 10.0, 10.4, 0.3, 40)
    _step(rec, 2, "decode", 10.5, 10.7, 0.19, 60)
    _step(rec, 3, "decode", 10.8, 11.0, 0.17, 50)
    _step(rec, 4, "idle", 11.0, 11.001, 0.0, 50)
    _step(rec, 5, "decode", 19.9, 20.1, 0.1, 199)    # ends after it
    bench_reqs, times = [], [
        # (due, t_submit, t_admit, t_first)
        (9.0, 9.1, 9.2, 9.9),          # due before the window
        (11.0, 11.01, 11.02, 12.0),
        (12.0, 12.01, 12.5, 14.0),
        (13.0, 13.01, 13.03, None),    # no first token: counts to stop
        (19.0, 19.01, None, None),     # never admitted: counts to stop
    ]
    for rid, (due, sub, adm, first) in enumerate(times):
        req = Request(rid=rid, prompt=[1, 2], mode="no_think", budget=4,
                      t_submit=sub)
        req.t_admit, req.t_first = adm, first
        rec.track(req)
        bench_reqs.append(RequestRecord(rid, due, sub + 0.001, 4, 2))
    rec.finish(rec._live[0])
    yield rec, _ctx(bench_reqs)
    del rec


def test_readers_on_a_hand_built_recorder(hand_built):
    rec, ctx = hand_built
    assert telemetry.current() is rec
    read = {n: load_reader(ROOT, n)(ctx) for n in READERS}
    waits = sorted([0.01, 0.49, 0.02, 25.0 - 19.01])
    assert read["admit_wait_ms.p90"] == pytest.approx(
        1e3 * (waits[2] + 0.7 * (waits[3] - waits[2])))
    assert read["prefill_ms.p50"] == pytest.approx(1e3 * 1.5)
    assert read["host_ms.step"] == pytest.approx(1e3 * (0.1 + 0.01 + 0.03)
                                                 / 3)
    assert read["wait_ms.decode"] == pytest.approx(1e3 * (0.19 + 0.17) / 2)
    assert read["kv_pages_used.peak"] == pytest.approx(100 * 60 / 200)


@pytest.mark.parametrize("stall, admit_waits, prefills", [
    # request 4 was due before the stall and submitted after it; request
    # 3, never given a first token, sat in the engine across it
    ((18.5, 19.005), [0.01, 0.49], [0.98, 1.5]),
    # request 2 sat in the engine across it, between submit and first token
    ((12.2, 12.3), [0.01, 0.02, 25.0 - 19.01], [0.98, 25.0 - 13.03]),
])
def test_requests_held_by_the_profiler_are_left_out(hand_built, stall,
                                                    admit_waits, prefills):
    rec, ctx = hand_built
    ctx.record.stalls = [stall]
    lo, hi = sorted(admit_waits)[-2:]
    rank = 0.9 * (len(admit_waits) - 1) - (len(admit_waits) - 2)
    assert load_reader(ROOT, "admit_wait_ms.p90")(ctx) == pytest.approx(
        1e3 * (lo + rank * (hi - lo)))
    assert load_reader(ROOT, "prefill_ms.p50")(ctx) == pytest.approx(
        1e3 * sum(prefills) / 2)


def test_readers_return_none_on_an_empty_recorder():
    rec = _recorder()
    ctx = _ctx([RequestRecord(7, 11.0, 11.0, 4, 2)])  # unknown to it
    for name in READERS:
        assert load_reader(ROOT, name)(ctx) is None, name
    del rec


def test_readers_return_none_without_a_recorder(hand_built, monkeypatch):
    """A program without serving/telemetry.py, as before it existed."""
    import repro.serving
    _, ctx = hand_built
    monkeypatch.delattr(repro.serving, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    for name in READERS:
        assert load_reader(ROOT, name)(ctx) is None, name
