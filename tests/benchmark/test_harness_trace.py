"""The trace reduction on a small constructed trace: busy union, idle
gaps labelled by span, program time inside requests, roofline."""

from __future__ import annotations

import pytest

import tracereduce as T
from tracereduce import Event, Trace


def _trace():
    # window [0, 100] ns on one chip; two requests, harness gaps between
    return Trace(
        ops={0: [Event(10, 20, "%fusion.1 = f32 fusion(x)"),
                 Event(15, 30, "%device_copy_with_checksum_chunk.3 = (f32) custom-call(x)"),
                 Event(50, 60, "%device_copy_with_checksum_chunk.4 = (f32) custom-call(x)"),
                 Event(120, 130, "%late = f32 fusion(x)")]},
        modules={0: [Event(10, 30, "jit__chunked_copy_csum(77)"),
                     Event(50, 60, "jit__chunked_copy_csum(77)"),
                     Event(62, 64, "jit_other(5)")]},
        spans={"bench.window": [(0, 100)],
               "bench.request": [(7, 40), (45, 70), (90, 110)],
               "bench.between": [(0, 7), (40, 45), (70, 90)]},
    )


def test_interval_arithmetic():
    assert T.union([(5, 9), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 9)]
    assert T.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert T.gaps([(10, 30), (50, 60)], 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert T.measure([(0, 3), (5, 9)]) == 7


def test_busy_idle_and_window():
    tr = _trace()
    assert T.window_s(tr) == pytest.approx(100e-9)
    # union of (10,20),(15,30),(50,60); the op after the window is out
    assert T.busy_s(tr, [0]) == pytest.approx(30e-9)
    assert T.idle_pct(tr, [0]) == pytest.approx(70.0)


def test_idle_gaps_labelled_by_open_span():
    gaps = T.idle_gaps(_trace(), [0])
    # (60,100): request 60-70 and 90-100 = 20, between 70-90 = 20 ->
    # tie keeps the first found (the program's host path);
    # (30,50): request 30-40 + 45-50 = 15 against between 5;
    # (0,10): between 0-7 against request 7-10
    assert [g[0] for g in gaps] == ["bench.request", "bench.request", "bench.between"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 20e-9, 10e-9])


def test_idle_gap_labels_name_the_chip_on_several():
    tr = _trace()
    tr.ops[1] = [Event(0, 100, "%copy.1 = f32 copy(x)")]
    gaps = T.idle_gaps(tr, [0, 1])
    assert all(g[0].startswith("TPU_0 ") for g in gaps)
    assert T.idle_pct(tr, [0, 1]) == pytest.approx(35.0)  # mean of 70 and 0


def test_host_only_counts_requests_inside_the_window():
    seconds, n = T.host_only_s(_trace(), [0])
    # requests (7,40) and (45,70) are inside; open 58 ns, 30 of it busy
    assert n == 2
    assert seconds == pytest.approx(28e-9)


def test_top_ops_add_up_chunks_by_kind():
    ops = dict(T.top_ops(_trace(), [0]))
    assert ops["device_copy_with_checksum_chunk"] == pytest.approx(25e-9)
    assert ops["fusion"] == pytest.approx(10e-9)
    assert "late" not in ops


def test_program_time_and_roofline():
    import spec
    from bench_helpers import REPO

    tr = _trace()
    kernel_s = T.program_time_in_requests(tr, [0], r"_chunked_copy_csum")
    assert kernel_s == pytest.approx(30e-9)  # jit_other is not counted

    class Run:
        trace = tr
        chips = [0]
        peaks = {"hbm_bytes_per_s": 1e9}

        class bench:
            hops_per_request = 2
            frame_bytes = 3

    cell = spec.load_cell("echo_1chip.bulk64m", REPO)
    read = spec.metric_reader(cell, "transmit_roofline_pct")
    # least: 2 requests x 2 hops x (2 x 3 B) / 1e9 B/s = 24 ns of 30 ns
    assert read(Run) == pytest.approx(80.0)
    Run.trace = Trace(ops=tr.ops, modules={0: []}, spans=tr.spans)
    assert read(Run) is None  # no transmit program ran: nothing to read
