"""The one closed-loop load generator.

Every caller is a closed loop: it issues its next request only after
the previous one has completed (the call returned without failure and
every device array of the reply is ready).  Callers are threads of this
one process, started together at the window's opening; each stops
issuing once the window has closed and finishes the request it has in
flight, so that every answer due in the window can be checked.

A caller is any object with ``call(k) -> bool`` (issue the caller's
k-th request and wait for it); which request that is comes from the
traffic mix and the seed, inside the system adapter.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


@dataclass
class WindowLog:
    t0_ns: int
    t1_ns: int
    start_ns: np.ndarray  # per request issued in the window
    end_ns: np.ndarray
    ok: np.ndarray  # bool
    between_ns: List[int] = field(default_factory=list)  # harness gaps
    unfinished: int = 0  # callers still in a request after the wait

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def completed_mask(self) -> np.ndarray:
        """Requests that succeeded and completed inside the window."""
        return self.ok & (self.end_ns <= self.t1_ns)

    def latencies_ms(self) -> List[float]:
        """Every request issued in the window that succeeded, also one
        that completed after the close: a late answer's wait counts."""
        m = self.ok
        return list(((self.end_ns[m] - self.start_ns[m]) / 1e6).tolist())

    @property
    def attempted(self) -> int:
        return int(len(self.ok)) + self.unfinished

    @property
    def failed(self) -> int:
        return int((~self.ok).sum()) + self.unfinished


def _nullcontext(_name):
    return contextlib.nullcontext()


def run_window(callers, seconds: float,
               annotate: Optional[Callable[[str], object]] = None,
               during: Optional[Callable[[int], None]] = None,
               drain_s: float = 120.0) -> WindowLog:
    """Run every caller in a closed loop for ``seconds``.

    ``annotate(name)`` returns a context manager (the traced run passes
    ``jax.profiler.TraceAnnotation``): each request runs under
    ``bench.request`` and the harness's own bookkeeping between two
    requests under ``bench.between``.  ``during(t0_ns)`` runs on the
    calling thread while the window is open (the traced run starts and
    stops the profiler there)."""
    ann = annotate or _nullcontext
    n = len(callers)
    logs = [([], [], []) for _ in range(n)]
    between = [[] for _ in range(n)]
    barrier = threading.Barrier(n + 1)
    t = {"t0": 0, "t1": 0}
    errors: List[BaseException] = []

    def loop(i: int):
        caller = callers[i]
        starts, ends, oks = logs[i]
        barrier.wait()
        t1 = t["t1"]
        k = 0
        try:
            while True:
                b0 = time.perf_counter_ns()
                if b0 >= t1:
                    break
                with ann("bench.request"):
                    s = time.perf_counter_ns()
                    ok = caller.call(k)
                    e = time.perf_counter_ns()
                with ann("bench.between"):
                    starts.append(s)
                    ends.append(e)
                    oks.append(bool(ok))
                    k += 1
                between[i].append(time.perf_counter_ns() - e)
        except BaseException as exc:  # reported by the main thread
            errors.append(exc)

    threads = [threading.Thread(target=loop, args=(i,), daemon=True,
                                name=f"bench-caller-{i}") for i in range(n)]
    for th in threads:
        th.start()
    t["t0"] = time.perf_counter_ns()
    t["t1"] = t["t0"] + int(seconds * 1e9)
    barrier.wait()
    if during is not None:
        during(t["t0"])
    left = t["t1"] - time.perf_counter_ns()
    if left > 0:
        time.sleep(left / 1e9)
    deadline = time.monotonic() + drain_s
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    if errors:
        raise errors[0]
    starts, ends, oks = [], [], []
    for s, e, o in logs:
        starts += s
        ends += e
        oks += o
    return WindowLog(
        t0_ns=t["t0"], t1_ns=t["t1"],
        start_ns=np.asarray(starts, np.int64),
        end_ns=np.asarray(ends, np.int64),
        ok=np.asarray(oks, bool),
        between_ns=[x for b in between for x in b],
        unfinished=sum(1 for th in threads if th.is_alive()),
    )


class Reservoir:
    """A uniform sample of at most ``k`` items from a stream, drawn
    with a seeded generator (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.items: list = []
        self.seen = 0

    def clear(self) -> None:
        self.items.clear()
        self.seen = 0

    def offer(self, make_item) -> None:
        """``make_item()`` is called only when the item is kept."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(make_item())
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = make_item()
