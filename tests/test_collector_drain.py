"""The Collector's drain: short slices, same folded contents.

Each test drives a private Collector by hand (no drain thread), so the
slicing is deterministic: ``drain_slice()`` folds what one GIL hold of
the drain thread folds.
"""

import math
import sqlite3
import threading
import time

import pytest

from incubator_brpc_tpu.metrics import dump_exposed
from incubator_brpc_tpu.metrics import collector as collector_mod
from incubator_brpc_tpu.metrics.collector import Collected, Collector, get_collector
from incubator_brpc_tpu.observability import latency_breakdown, span as span_mod
from incubator_brpc_tpu.observability.contention import ContentionSample, _profiler
from incubator_brpc_tpu.observability.span import Span, SpanDB
from incubator_brpc_tpu.utils.flags import set_flag


class _ManualCollector(Collector):
    """A Collector whose queue only the test drains."""

    def _start_drain(self):
        return None


def _drain_all(c: Collector) -> None:
    while c.drain_slice():
        pass


def _spans(n: int, service: str, trace_id: int = 0):
    """n finished spans with deterministic stamps and several phases."""
    out = []
    for i in range(n):
        kind = ("client", "server", "collective")[i % 3]
        s = Span(kind, service, f"M{i % 4}")
        s.trace_id = trace_id or (i + 1)
        s.start_us = 1_000_000 + 10 * i
        s.received_us = s.start_us + 1 + i % 7
        s.dequeued_us = s.received_us + 2 + i % 11
        s.parse_done_us = s.dequeued_us + 3
        s.callback_start_us = s.parse_done_us + i % 5
        s.callback_done_us = s.callback_start_us + 10 + i % 13
        if kind == "collective":
            s.placed_us = s.start_us + 40 + i % 17
        s.end_us = s.start_us + 100 + i % 29
        out.append(s)
    return out


def _counts(service: str) -> dict:
    snap = latency_breakdown.snapshot()
    return {
        (m, p): v["count"]
        for m, phases in snap.items()
        if m.startswith(service)
        for p, v in phases.items()
    }


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v - before.get(k, 0)}


def test_sliced_drain_matches_single_batch_fold(monkeypatch):
    service = "CollectorSliceSvc"
    spans = _spans(1000, service)

    # the single-batch fold: every span folded on its own, in order
    ref_db = SpanDB()
    monkeypatch.setattr(span_mod, "_span_db", ref_db)
    before = _counts(service)
    for s in spans:
        s.dump_and_destroy()
    ref_counts = _delta(_counts(service), before)

    new_db = SpanDB()
    monkeypatch.setattr(span_mod, "_span_db", new_db)
    c = _ManualCollector()
    for s in spans:
        c.submit(s)
    before = _counts(service)
    _drain_all(c)
    new_counts = _delta(_counts(service), before)

    def mine(db):
        return [id(s) for s in db.recent(2048) if s.service == service]

    assert mine(new_db) == mine(ref_db) == [id(s) for s in spans]
    assert new_counts == ref_counts
    assert sum(new_counts.values()) > 1000  # phases were folded, not only totals
    assert c.drained.get_value() == 1000


@pytest.mark.parametrize(
    "n", [1, Collector.SLICE - 1, Collector.SLICE, Collector.SLICE + 1, 1000]
)
def test_slices_are_bounded(n):
    sizes = []

    class Sample(Collected):
        @classmethod
        def dump_many(cls, samples):
            sizes.append(len(samples))

        def speed_limit(self):
            return 1 << 20

    c = _ManualCollector()
    for _ in range(n):
        c.submit(Sample())
    _drain_all(c)
    assert c.slices.get_value() == math.ceil(n / Collector.SLICE)
    assert len(sizes) == math.ceil(n / Collector.SLICE)
    assert max(sizes) <= Collector.SLICE
    assert sum(sizes) == c.drained.get_value() == n
    assert c.slice_us.count() == c.slices.get_value()
    assert not c._q


def test_contention_samples_still_drained(monkeypatch):
    monkeypatch.setattr(span_mod, "_span_db", SpanDB())
    c = _ManualCollector()
    before = _profiler.total_samples
    spans = _spans(90, "CollectorMixSvc")
    stack = ("test_collector_drain.py:contention",)
    for i, s in enumerate(spans):
        c.submit(s)
        if i % 3 == 0:
            c.submit(ContentionSample(1000 + i, stack))
    _drain_all(c)
    assert _profiler.total_samples - before == 30
    assert c.drained.get_value() == 120
    assert c.slices.get_value() == math.ceil(120 / Collector.SLICE)
    assert [s for s in span_mod._span_db.recent(2048)] == spans


def test_multi_slice_drain_reaches_sqlite(tmp_path, monkeypatch):
    db_file = str(tmp_path / "rpcz.sqlite")
    monkeypatch.setattr(span_mod, "_span_db", SpanDB())
    assert set_flag("rpcz_db_path", db_file)
    try:
        spans = _spans(100, "CollectorSqliteSvc", trace_id=0x5EED)
        c = _ManualCollector()
        for s in spans:
            c.submit(s)
        _drain_all(c)
        assert c.slices.get_value() == math.ceil(100 / Collector.SLICE) > 1
        rows = sqlite3.connect(db_file).execute(
            "SELECT span_id FROM spans WHERE trace_id=? ORDER BY rowid",
            (0x5EED,),
        ).fetchall()
        assert [r[0] for r in rows] == [s.span_id for s in spans]
        assert len(span_mod._span_db.persisted_by_trace(0x5EED)) == 100
    finally:
        set_flag("rpcz_db_path", "")


def test_drain_sleeps_between_slices(monkeypatch):
    """Between slices the drain blocks in a short sleep (a waiting
    thread takes the GIL); once the queue is empty it waits a period."""
    sleeps = []
    me = threading.current_thread()

    class _Stop(Exception):
        pass

    class _Clock:
        perf_counter_ns = staticmethod(time.perf_counter_ns)
        monotonic = staticmethod(time.monotonic)

        @staticmethod
        def sleep(s):
            # the process's own drain thread shares this module: it
            # sleeps as usual
            if threading.current_thread() is not me:
                return time.sleep(s)
            sleeps.append(s)
            if sleeps.count(Collector._DRAIN_PERIOD_S) > 1:
                raise _Stop

    class Sample(Collected):
        def speed_limit(self):
            return 1 << 20

    c = _ManualCollector()
    for _ in range(100):
        c.submit(Sample())
    monkeypatch.setattr(collector_mod, "time", _Clock)
    with pytest.raises(_Stop):
        c._drain()
    period, pause = Collector._DRAIN_PERIOD_S, Collector._YIELD_S
    slices = math.ceil(100 / Collector.SLICE)
    assert slices > 1
    assert sleeps == [period] + [pause] * (slices - 1) + [period]
    assert 0 < pause < period <= 0.01
    assert c._q.maxlen >= 4096
    assert c.drained.get_value() == 100


def test_collector_counters_in_vars():
    c = get_collector()
    before = c.drained.get_value()
    done = threading.Event()

    class Sample(Collected):
        def dump_and_destroy(self):
            done.set()

    Sample().submit()
    assert done.wait(5)
    deadline = time.monotonic() + 5
    while c.drained.get_value() <= before and time.monotonic() < deadline:
        time.sleep(0.01)
    names = dict(dump_exposed("rpcz_collector_*"))
    for name in (
        "rpcz_collector_drained",
        "rpcz_collector_slices",
        "rpcz_collector_slice_us_latency",
        "rpcz_collector_slice_us_latency_50",
        "rpcz_collector_slice_us_max_latency",
    ):
        assert name in names, sorted(names)
    assert int(names["rpcz_collector_drained"]) > before
    assert int(names["rpcz_collector_slices"]) >= 1
