"""Metric arithmetic: a rate over the whole window and a tail over
every request, with a stall in the window showing in both."""

from __future__ import annotations

import time

import numpy as np
import pytest

from generator import Reservoir, WindowLog, run_window
from stats import percentile, rate


def test_percentile_is_over_every_value():
    lat = [1.0] * 49 + [500.0]  # one stall among fifty
    assert percentile(lat, 99) == 500.0
    assert percentile(lat, 50) == 1.0
    with pytest.raises(ValueError):
        percentile([], 99)


def test_rate_counts_the_window_and_the_tail_every_answer():
    log = WindowLog(
        t0_ns=0, t1_ns=1_000,
        start_ns=np.array([0, 100, 900, 950]),
        end_ns=np.array([90, 800, 1_200, 990]),
        ok=np.array([True, True, True, False]),
        unfinished=1,
    )
    # the answer that came after the close is late, and its wait counts
    assert log.latencies_ms() == pytest.approx([90e-6, 700e-6, 300e-6])
    assert log.attempted == 5 and log.failed == 2
    assert rate(int(log.completed_mask().sum()), log.window_s) == 2e6


class _Stalling:
    """1 ms a request, and one 300 ms stall."""

    def call(self, k):
        time.sleep(0.3 if k == 50 else 0.001)
        return True


def test_a_stall_in_the_window_shows_in_rate_and_tail():
    log = run_window([_Stalling()], 1.0)
    done = int(log.completed_mask().sum())
    # without the stall ~1000 requests; the rate is taken over all time
    assert rate(done, log.window_s) < 800
    assert max(log.latencies_ms()) >= 300
    assert percentile(log.latencies_ms(), 100) >= 300


def test_reservoir_is_seeded_and_bounded():
    picks = []
    for _ in range(2):
        r = Reservoir(5, np.random.default_rng(7))
        for i in range(100):
            r.offer(lambda i=i: i)
        picks.append(list(r.items))
    assert picks[0] == picks[1] and len(picks[0]) == 5
