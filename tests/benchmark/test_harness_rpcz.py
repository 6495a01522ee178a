"""The four host-path readers on constructed rpcz captures, and traced
runs of each cell reporting them: requests grouped by trace id, those
that straddle the capture's edges left out, and the four parts plus the
uncovered remainder adding up to the mean client span."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

import rpcz_capture as RC
from bench_helpers import REPO, run_cell

METRICS = ("client_us", "fabric_us", "cq_wait_us", "service_us")


def _span(kind, trace, sid, parent, **stamps):
    base = dict(kind=kind, trace_id=trace, span_id=sid, parent_span_id=parent,
                service="ici" if kind == "collective" else "EchoService",
                error_code=0)
    base.update(stamps)
    return SimpleNamespace(**base)


def _request(trace, t, server_wait=0, client_wait=0, server_service=None):
    """One echo at offset ``t`` (µs): client work 10 + 8, legs placed in
    18 and 7, waits in the two completion queues, handler 14, and gaps
    between the parts that no part covers (2+1+3+3+1 = 10)."""
    sw, cw = server_wait, client_wait
    c = _span("client", trace, 1, 0, start_us=t, response_write_us=t + 10,
              received_us=t + 61 + sw, dequeued_us=t + 62 + sw + cw,
              end_us=t + 70 + sw + cw)
    leg1 = _span("collective", trace, 2, 1, start_us=t + 12, placed_us=t + 30,
                 end_us=t + 69 + sw + cw)
    s = _span("server", trace, 3, 1, received_us=t + 31,
              dequeued_us=t + 33 + sw, parse_done_us=t + 35 + sw,
              callback_start_us=t + 36 + sw, callback_done_us=t + 50 + sw,
              response_write_us=t + 52 + sw, start_us=t + 35 + sw,
              end_us=t + 68 + sw + cw)
    if server_service:
        s.service = server_service
    leg2 = _span("collective", trace, 4, 3, start_us=t + 53 + sw,
                 placed_us=t + 60 + sw, end_us=t + 67 + sw + cw)
    return [c, leg1, s, leg2]


def _capture(*groups, start=1000, stop=9000, overflow=0):
    return SimpleNamespace(spans=[s for g in groups for s in g],
                           start_us=start, stop_us=stop, overflow=overflow)


def test_inline_request_parts_and_remainder():
    cap = _capture(_request(7, 2000))
    (r,) = RC.requests(cap)
    assert [RC.PARTS[m](r) for m in METRICS] == [18, 25, 3, 14]
    s = RC.summary(cap)
    assert s["complete_requests"] == 1 and s["client_span_us"] == 70
    assert s["remainder_us"] == pytest.approx(10)  # the constructed gaps
    assert s["covered_pct"] == pytest.approx(100 * 60 / 70)


def test_queued_delivery_waits_land_in_cq_wait():
    cap = _capture(_request(7, 2000, server_wait=40, client_wait=25),
                   _request(8, 3000))
    means = {m: RC.mean_of(RC.PARTS[m], cap) for m in METRICS}
    # waits of 40 + 25 on one of two requests; the other parts unchanged
    assert means == {"client_us": 18, "fabric_us": 25,
                     "cq_wait_us": pytest.approx((3 + 65 + 3) / 2),
                     "service_us": 14}
    s = RC.summary(cap)
    assert s["client_span_us"] == pytest.approx((135 + 70) / 2)
    assert sum(s[m] for m in METRICS) + s["remainder_us"] == pytest.approx(
        s["client_span_us"])
    assert s["remainder_us"] == pytest.approx(10)


def test_requests_straddling_the_capture_edges_are_left_out():
    inside = _request(1, 2000)
    early = _request(2, 950)  # client span starts before the capture armed
    late = _request(3, 8950)  # and one that ends after it stopped
    cap = _capture(inside, early, late)
    assert [r.client.trace_id for r in RC.requests(cap)] == [1]
    # an interval still open ends at the last stamp the capture saw
    cap.stop_us = 0
    assert sorted(r.client.trace_id for r in RC.requests(cap)) == [1, 3]


def test_incomplete_requests_are_left_out():
    no_server = [s for s in _request(1, 2000) if s.kind != "server"]
    one_leg = _request(2, 3000)[:3]
    unstamped = _request(3, 4000)
    del unstamped[3].placed_us  # a leg that never placed
    failed = _request(4, 5000)
    failed[0].error_code = 1008
    cap = _capture(no_server, one_leg, unstamped, failed)
    assert RC.requests(cap) == []
    for m in METRICS:
        assert RC.mean_of(RC.PARTS[m], cap) is None
    assert RC.summary(cap) == {"spans": len(cap.spans), "overflow": 0,
                               "complete_requests": 0}


def test_redis_spans_group_by_the_joined_trace():
    cap = _capture(_request(5, 2000, server_service="redis"),
                   _request(6, 3000, server_service="redis"))
    reqs = RC.requests(cap)
    assert len(reqs) == 2 and {r.server.service for r in reqs} == {"redis"}
    assert RC.mean_of(RC.service_us, cap) == 14


def test_readers_read_the_programs_capture(monkeypatch):
    import spec

    cell = spec.load_cell("ycsb_1kb.b", REPO)
    assert {m["name"] for m in cell.per_layer} >= {f"{m}.small" for m in METRICS}
    cap = _capture(_request(7, 2000), _request(8, 3000, server_wait=10))
    monkeypatch.setattr(RC, "last_capture", lambda: cap)
    traced, untraced = SimpleNamespace(trace=object()), SimpleNamespace(trace=None)
    want = {"client_us": 18, "fabric_us": 25, "cq_wait_us": 8, "service_us": 14}
    for m in METRICS:
        read = spec.metric_reader(cell, m + ".small")
        assert read(traced) == pytest.approx(want[m])
        assert read(untraced) is None
    # a program without the capture (an older checkout): nothing to read
    monkeypatch.setattr(RC, "last_capture", lambda: None)
    assert spec.metric_reader(cell, "client_us.bulk")(traced) is None


def test_a_program_without_last_capture_reads_none(monkeypatch):
    from incubator_brpc_tpu.observability import span

    monkeypatch.delattr(span, "last_capture")
    assert RC.last_capture() is None
    assert RC.mean_of(RC.client_us) is None


@pytest.mark.parametrize("workload,group", [
    ("echo_1chip.bulk64m", "bulk"), ("ycsb_1kb.b", "small"),
    ("echo_1chip.small4k", "small"),
])
def test_traced_run_reports_the_four_parts(tiny, interpret_kernels, workload,
                                           group):
    rc, _, res = run_cell(tiny, workload, seconds=1.5, trace=1)
    assert rc == 0 and res["correct"] is True
    for m in METRICS:
        assert res["metrics"][f"{m}.{group}"]["value"] >= 0, (m, res["metrics"])
    assert res["metrics"][f"service_us.{group}"]["value"] > 0
