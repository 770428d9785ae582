"""The dispatcher's own trace (PR 36): one ``dispatch`` record a traced
cycle in the server's ``TraceLog``, four spans that partition the
dispatcher thread's time, and results handed back before tracing's own
bookkeeping. A stand-in model behind ``QueryBatcher``, no HTTP."""

from __future__ import annotations

import threading
import time

import pytest

from predictionio_tpu.obs.trace import Trace, TraceLog, span
from predictionio_tpu.serving import batcher as batcher_mod
from predictionio_tpu.serving.batch_policy import FixedBatchPolicy
from predictionio_tpu.serving.batcher import (
    QueryBatcher,
    QueryDeadlineExceeded,
)
from predictionio_tpu.utils.resilience import deadline_scope

FOUR = ("dispatcher.idle", "dispatcher.collect", "dispatcher.dispatch",
        "dispatcher.handoff")


class _Deployed:
    """``query_batch`` records two phases through the ambient trace, as
    a template's ``batch_predict`` does."""

    def __init__(self, fail_batch: bool = False):
        self.fail_batch = fail_batch

    def query_batch(self, queries):
        if self.fail_batch:
            raise RuntimeError("batch path down")
        with span("dispatch.prepare"):
            pass
        with span("dispatch.enqueue"):
            time.sleep(0.002)
        return [("batched", q) for q in queries]

    def query(self, q):
        return ("single", q)

    def record_served(self, dt):
        pass


def _records(log: TraceLog) -> list[Trace]:
    with log._lock:
        return [t for t in log._ring if t.name == "dispatch"]


def _by_name(trace: Trace) -> dict:
    """name -> (start, end) on the perf_counter clock."""
    out = {}
    for name, _, _, start, dur in trace.spans():
        assert name not in out, f"{name} recorded twice"
        out[name] = (trace.start_perf + start, trace.start_perf + start + dur)
    return out


def _settled(log: TraceLog, n: int) -> list[Trace]:
    """The ``n`` dispatch records, once the dispatcher has filled the
    last one in (it does so after the futures are set)."""
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        records = _records(log)
        if len(records) >= n and all(
                "requests" in r.tags for r in records[:n]):
            return records
        time.sleep(0.005)
    raise AssertionError(f"{len(_records(log))} settled records, want {n}")


@pytest.fixture
def traced_batcher():
    made = []

    def make(deployed=None, wait_ms=0.0, batch_max=8):
        log = TraceLog(maxlen=1024)
        b = QueryBatcher(lambda: deployed or _Deployed(),
                         policy=FixedBatchPolicy(batch_max=batch_max,
                                                 wait_ms=wait_ms),
                         trace_log=log)
        made.append(b)
        return b, log

    yield make
    for b in made:
        b.close()


def test_four_spans_partition_the_dispatchers_time(traced_batcher):
    """Several cycles, some after a pause and some back to back (two
    submitters racing): consecutive records meet end to start and each
    one's spans sum to its cycle's end minus the previous cycle's."""
    b, log = traced_batcher(batch_max=1)
    riders = [Trace("queries.json") for _ in range(9)]
    for t in riders[:3]:
        b.submit({"q": id(t)}, trace=t)
        time.sleep(0.01)                      # the dispatcher goes idle

    def burst(mine):
        for t in mine:
            b.submit({"q": id(t)}, trace=t)

    threads = [threading.Thread(target=burst, args=(riders[3 + k::2],))
               for k in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
        assert not th.is_alive()
    records = _settled(log, 9)
    assert len(records) == 9
    spans = [_by_name(r) for r in records]
    # a dispatcher's first cycle has no idle span; every later one does
    assert set(spans[0]) == set(FOUR[1:])
    assert all(set(s) == set(FOUR) for s in spans[1:])
    prev_end = None
    for record, s in zip(records, spans):
        order = [s[n] for n in FOUR if n in s]
        for (_, a_end), (b_start, _) in zip(order, order[1:]):
            assert b_start == pytest.approx(a_end, abs=1e-6)
        start, end = order[0][0], order[-1][1]
        total = sum(e - st for st, e in order)
        assert total == pytest.approx(end - start, abs=1e-6)
        if prev_end is not None:
            # no hole and no overlap between cycles
            assert start == pytest.approx(prev_end, abs=1e-6)
            assert total == pytest.approx(end - prev_end, abs=1e-6)
        # the record's own origin and duration are the cycle's
        assert record.start_perf == pytest.approx(start, abs=1e-6)
        assert record.to_dict()["durationMs"] == pytest.approx(
            (end - start) * 1e3, abs=2e-3)
        assert all(sp["startMs"] >= 0 for sp in record.to_dict()["spans"])
        prev_end = end
    # the pauses show as idle, the back-to-back cycles as (nearly) none
    idle = [s["dispatcher.idle"][1] - s["dispatcher.idle"][0]
            for s in spans[1:]]
    assert idle[0] > 0.005 and idle[1] > 0.005
    assert min(idle) < 0.005


def test_record_and_riders_name_each_other(traced_batcher):
    b, log = traced_batcher(wait_ms=150.0)
    riders = [Trace("queries.json") for _ in range(3)]
    barrier = threading.Barrier(3)

    def go(t):
        barrier.wait()
        b.submit({"q": id(t)}, trace=t, key=str(id(t)))

    threads = [threading.Thread(target=go, args=(t,)) for t in riders]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    records = _settled(log, 1)
    named = {tid for r in records for tid in r.tags["requests"]}
    assert named == {t.trace_id for t in riders}
    by_id = {r.trace_id: r for r in records}
    for t in riders:
        record = by_id[t.tags["dispatch"]]
        assert t.trace_id in record.tags["requests"]
    for r in records:
        assert r.service == "engine"
        assert r.tags["batch"] == r.tags["traced"] == len(r.tags["requests"])
        assert r.tags["groups"] == r.tags["batch"]
        # the ring keeps names of its own: no batcher.* or dispatch.*
        # span on the record, so the readers of those see no new sample
        assert {s[0] for s in r.spans()} <= set(FOUR)


def test_record_is_in_the_ring_before_a_riders_future_is_done(
        traced_batcher, monkeypatch):
    """Whoever has an answer finds the ring in the order dispatch, then
    request: the record is there when the first future is set, and the
    rider's own span with it."""
    b, log = traced_batcher()
    seen = []
    real = batcher_mod.Future.set_result

    def spying(fut, result):
        seen.append(([t.name for t in _records(log)],
                     [s[0] for s in rider.spans()]))
        real(fut, result)

    monkeypatch.setattr(batcher_mod.Future, "set_result", spying)
    rider = Trace("queries.json")
    assert b.submit({"q": 1}, trace=rider) == ("batched", {"q": 1})
    in_ring, rider_spans = seen[0]
    assert in_ring == ["dispatch"]
    # results first: the one span the wake-up reads is on the trace,
    # the phases are not yet
    assert rider_spans[-1] == "batcher.device_dispatch"
    assert "dispatch.prepare" not in rider_spans


def test_wake_starts_where_the_dispatch_span_ends_and_phases_follow(
        traced_batcher):
    b, log = traced_batcher()
    rider = Trace("queries.json")
    b.submit({"q": 1}, trace=rider)
    (record,) = _settled(log, 1)
    spans = {s[0]: s for s in rider.spans()}
    dd, wake = spans["batcher.device_dispatch"], spans["batcher.wake"]
    assert wake[3] == pytest.approx(dd[3] + dd[4], abs=1e-9)
    # by the time the record's hand-off is written the phases are on
    # the rider, as children of its dispatch span and inside it
    assert "dispatcher.handoff" in _by_name(record)
    for name in ("dispatch.prepare", "dispatch.enqueue"):
        assert spans[name][1] == dd[2]
        assert dd[3] - 1e-9 <= spans[name][3]
        assert spans[name][3] + spans[name][4] <= dd[3] + dd[4] + 1e-9
    # and the rider's dispatch span is the record's dispatch interval
    lo, hi = _by_name(record)["dispatcher.dispatch"]
    assert rider.start_perf + dd[3] == pytest.approx(lo, abs=1e-9)
    assert rider.start_perf + dd[3] + dd[4] == pytest.approx(hi, abs=1e-9)


def test_the_fallback_path_closes_its_cycle(traced_batcher):
    b, log = traced_batcher(deployed=_Deployed(fail_batch=True))
    first, second = Trace("queries.json"), Trace("queries.json")
    assert b.submit({"q": 1}, trace=first) == ("single", {"q": 1})
    assert b.submit({"q": 2}, trace=second) == ("single", {"q": 2})
    records = _settled(log, 2)
    assert set(_by_name(records[0])) == set(FOUR[1:])
    s = _by_name(records[1])
    assert set(s) == set(FOUR)
    assert s["dispatcher.idle"][0] == pytest.approx(
        _by_name(records[0])["dispatcher.handoff"][1], abs=1e-6)
    # dispatcher.dispatch covers the failed batch and the retry
    retry = {x[0]: x for x in second.spans()}["batcher.fallback_predict"]
    lo, hi = s["dispatcher.dispatch"]
    assert lo <= second.start_perf + retry[3]
    assert second.start_perf + retry[3] + retry[4] <= hi + 1e-9
    assert second.tags["dispatch"] == records[1].trace_id


def test_a_cycle_whose_riders_all_expired_closes_too(traced_batcher):
    """The 30 ms budget dies inside the 200 ms window: nothing is
    dispatched, the cycle is idle + collect, and the next cycle starts
    where it ended."""
    b, log = traced_batcher(wait_ms=200.0)
    warm, late, after = (Trace("queries.json") for _ in range(3))
    b.submit({"q": 0}, trace=warm)
    with deadline_scope(0.03):
        with pytest.raises(QueryDeadlineExceeded):
            b.submit({"q": 1}, trace=late, timeout=5.0)
    b.submit({"q": 2}, trace=after)
    records = _settled(log, 3)
    expired = next(r for r in records
                   if r.tags["requests"] == [late.trace_id])
    s = _by_name(expired)
    assert set(s) == {"dispatcher.idle", "dispatcher.collect"}
    assert expired.tags["batch"] == 0 and expired.tags["traced"] == 1
    assert s["dispatcher.collect"][0] == pytest.approx(
        s["dispatcher.idle"][1], abs=1e-6)
    nxt = _by_name(records[records.index(expired) + 1])
    assert nxt["dispatcher.idle"][0] == pytest.approx(
        s["dispatcher.collect"][1], abs=1e-6)
    assert late.tags["dispatch"] == expired.trace_id


def test_an_untraced_cycle_records_nothing_and_resets_the_origin(
        traced_batcher):
    """No traced rider: no record, no Trace, no clock read for one; the
    next traced cycle has no previous end to count from, so no idle."""
    b, log = traced_batcher()
    b.submit({"q": 1}, trace=Trace("queries.json"))
    _settled(log, 1)
    b.submit({"q": 2})
    assert len(_records(log)) == 1
    b.submit({"q": 3}, trace=Trace("queries.json"))
    records = _settled(log, 2)
    assert set(_by_name(records[1])) == set(FOUR[1:])


def test_tracing_off_the_dispatcher_makes_no_trace(monkeypatch):
    made = []

    class Spy(Trace):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(batcher_mod, "Trace", Spy)
    log = TraceLog()
    b = QueryBatcher(lambda: _Deployed(), trace_log=log,
                     policy=FixedBatchPolicy(batch_max=4, wait_ms=0.0))
    try:
        for q in range(3):
            assert b.submit({"q": q}) == ("batched", {"q": q})
    finally:
        b.close()
    assert made == [] and log.snapshot() == []


def test_a_batcher_without_a_ring_still_tags_and_copies(traced_batcher):
    """The legacy constructor (no ``trace_log``): the record is made and
    not kept; riders get their spans and the tag all the same."""
    b = QueryBatcher(lambda: _Deployed(),
                     policy=FixedBatchPolicy(batch_max=4, wait_ms=0.0))
    try:
        rider = Trace("queries.json")
        b.submit({"q": 1}, trace=rider)
        deadline = time.monotonic() + 5
        while "dispatch" not in rider.tags and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        b.close()
    names = [s[0] for s in rider.spans()]
    assert "dispatch" in rider.tags
    assert {"batcher.queue_wait", "batcher.hold", "batcher.device_dispatch",
            "dispatch.prepare", "dispatch.enqueue", "batcher.wake"} \
        <= set(names)
