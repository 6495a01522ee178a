"""Collector — global bounded sampling pipeline.

Reference bvar/collector.{h,cpp} (collector.h:48-72): shared base for
rpcz spans and mutex-contention samples. Producers call
``Collected.submit()``; a speed limiter keeps collection below
`max_samples_per_second` (sampling, not backpressure: excess samples
are dropped), and a background drain thread folds what was queued.

The drain holds the GIL while it folds, and a serving thread that
comes back from a blocking call waits for it.  So it wakes every
``_DRAIN_PERIOD_S`` and folds at most ``SLICE`` samples a stretch,
grouped by class into one ``dump_many`` call each, with a short
blocking sleep between slices until the queue is empty.  Exposed on
the global collector (once the first sample is submitted):
``rpcz_collector_drained`` (samples folded), ``rpcz_collector_slices``
(slices run) and ``rpcz_collector_slice_us`` (each slice's hold).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, List, Optional

from incubator_brpc_tpu.metrics.latency_recorder import LatencyRecorder
from incubator_brpc_tpu.metrics.reducer import Adder

COLLECTOR_SAMPLING_BASE = 64
_MAX_PER_SECOND = 1000


class Collected:
    """Base for collectable samples (rpcz Span subclasses this)."""

    def submit(self):
        get_collector().submit(self)

    def dump_and_destroy(self):  # overridden
        pass

    @classmethod
    def dump_many(cls, samples: List["Collected"]) -> None:
        """Fold one slice's samples of this class, in order.  Override
        to pay per-slice work (locks, flag reads) once a slice."""
        for sample in samples:
            try:
                sample.dump_and_destroy()
            except Exception:
                pass

    def speed_limit(self) -> int:
        return _MAX_PER_SECOND


class Collector:
    # samples folded per GIL hold, and the blocking sleep between holds
    SLICE = 16
    _DRAIN_PERIOD_S = 0.01
    _YIELD_S = 0.0002

    def __init__(self):
        self._q: Deque[Collected] = deque(maxlen=4096)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._window_start = time.monotonic()
        # per-sample-class counts: rpcz spans declare a higher
        # speed_limit than contention samples, and a shared counter
        # would let heavy span traffic starve the other sample types
        self._window_counts: dict = {}
        self.dropped = 0
        self.collected = 0
        self.drained = Adder(0)
        self.slices = Adder(0)
        self.slice_us = LatencyRecorder()

    def submit(self, sample: Collected):
        now = time.monotonic()
        cls = type(sample)
        # over-limit fast path WITHOUT the lock: a dirty read of the
        # window counters may mis-drop/mis-admit a handful of samples
        # at the window edge (sampling is approximate by design), but
        # saturated producers — the RPC hot path under load — skip the
        # lock acquire entirely
        if (
            self._window_counts.get(cls, 0) >= sample.speed_limit()
            and now - self._window_start < 1.0
        ):
            self.dropped += 1
            return
        with self._lock:
            if now - self._window_start >= 1.0:
                self._window_start = now
                self._window_counts.clear()
            cnt = self._window_counts.get(cls, 0)
            if cnt >= sample.speed_limit():
                self.dropped += 1
                return
            self._window_counts[cls] = cnt + 1
            self._q.append(sample)
            self.collected += 1
            if self._thread is None:
                self._thread = self._start_drain()
            # No per-sample notify: the drain thread polls in rounds
            # (reference collector.cpp likewise sleeps between grabs).
            # Waking it per sample costs a futex wake + context switch
            # on the RPC hot path — thousands per second under load.

    def _start_drain(self) -> threading.Thread:
        t = threading.Thread(
            target=self._drain, daemon=True, name="tpubrpc-collector"
        )
        t.start()
        return t

    def _drain(self):
        while True:
            time.sleep(self._DRAIN_PERIOD_S)
            while self.drain_slice():
                time.sleep(self._YIELD_S)  # blocks: a waiter takes the GIL

    def drain_slice(self) -> bool:
        """Fold the oldest ``SLICE`` queued samples (fewer if fewer are
        queued); returns whether any are left."""
        t0 = time.perf_counter_ns()
        with self._lock:
            q = self._q
            batch = [q.popleft() for _ in range(min(len(q), self.SLICE))]
        if batch:
            by_class: dict = {}
            for sample in batch:
                by_class.setdefault(type(sample), []).append(sample)
            for cls, samples in by_class.items():
                try:
                    cls.dump_many(samples)
                except Exception:
                    pass
            self.drained << len(batch)
            self.slices << 1
            self.slice_us.update_batched((time.perf_counter_ns() - t0) // 1000)
        return bool(self._q)


_collector: Optional[Collector] = None
_collector_lock = threading.Lock()


def get_collector() -> Collector:
    global _collector
    if _collector is None:
        with _collector_lock:
            if _collector is None:
                c = Collector()
                c.drained.expose("rpcz_collector_drained")
                c.slices.expose("rpcz_collector_slices")
                c.slice_us.expose("rpcz_collector_slice_us")
                _collector = c
    return _collector
