"""Reduce a profiler trace of the window to per-layer numbers.

The profiler's ``.xplane.pb`` is read once (:func:`from_profile`) into
a :class:`Trace` of plain intervals on one clock: each chip's device
operations and programs, and the harness's own host spans
(``bench.request`` around every request, ``bench.between`` around the
harness's bookkeeping between two requests, ``bench.window`` around the
traced stretch).  Everything after that is arithmetic on intervals,
tested on constructed traces in ``tests/benchmark``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPANS = ("bench.request", "bench.between", "bench.window")


@dataclass
class Event:
    start: float
    end: float
    name: str


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)  # per chip
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    def window(self) -> Interval:
        w = self.spans.get("bench.window") or []
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0]


def from_profile(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a Trace (the one place that knows
    the profiler's layout)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace(spans={k: [] for k in SPANS})
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = tr.ops.setdefault(chip, [])
                elif line.name == MODULES_LINE:
                    dest = tr.modules.setdefault(chip, [])
                else:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    dest.append(Event(s, s + float(e.duration_ns), e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in tr.spans:
                        s = float(e.start_ns)
                        tr.spans[e.name].append((s, s + float(e.duration_ns)))
    for v in tr.spans.values():
        v.sort()
    return tr


# ---- interval arithmetic ---------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def measure(merged: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in merged))


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (sorted, disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a union within [lo, hi]."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [g for g in out if g[1] > g[0]]


# ---- reductions ------------------------------------------------------------

def busy(tr: Trace, chip: int) -> List[Interval]:
    lo, hi = tr.window()
    return union(clip(((e.start, e.end) for e in tr.ops.get(chip, [])), lo, hi))


def busy_s(tr: Trace, chips: Sequence[int]) -> float:
    """Seconds in which an operation ran, averaged over ``chips``."""
    return sum(measure(busy(tr, c)) for c in chips) / len(chips) / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def idle_pct(tr: Trace, chips: Sequence[int]) -> float:
    """100 × (1 − busy / window), the mean of each chip's share."""
    return 100.0 * (1.0 - busy_s(tr, chips) / window_s(tr))


def completed_requests(tr: Trace) -> List[Interval]:
    """``bench.request`` spans that lie wholly inside the window."""
    lo, hi = tr.window()
    return [(s, e) for s, e in tr.spans.get("bench.request", [])
            if s >= lo and e <= hi]


def host_only_s(tr: Trace, chips: Sequence[int]) -> Tuple[float, int]:
    """(seconds in which some request was open and no chip ran an
    operation, number of requests completed in the window)."""
    lo, hi = tr.window()
    reqs = completed_requests(tr)
    open_ = union(reqs)
    dev = union(x for c in chips for x in busy(tr, c))
    return (measure(open_) - measure(intersect(open_, dev))) / 1e9, len(reqs)


def label_gap(tr: Trace, gap: Interval) -> str:
    """The harness span that covers most of an idle gap:
    ``bench.request`` (the program's host path), ``bench.between`` (the
    harness itself), or ``none``."""
    best, label = 0.0, "none"
    for name in ("bench.request", "bench.between"):
        cover = measure(intersect(union(clip(tr.spans.get(name, []), *gap)), [gap]))
        if cover > best:
            best, label = cover, name
    return label


def idle_gaps(tr: Trace, chips: Sequence[int], top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps as [label, seconds]; the label
    names the chip where the cell has several."""
    lo, hi = tr.window()
    found = []
    for c in chips:
        for g in gaps(busy(tr, c), lo, hi):
            found.append((g[1] - g[0], c, g))
    found.sort(key=lambda x: -x[0])
    out = []
    for dur, c, g in found[:top]:
        label = label_gap(tr, g)
        if len(chips) > 1:
            label = f"TPU_{c} {label}"
        out.append([label, dur / 1e9])
    return out


_SUFFIX = re.compile(r"\.\d+$")


def op_kind(name: str) -> str:
    """An XLA op event's HLO instruction name without its ``.N``
    suffix: ``%device_copy_with_checksum_chunk.12 = (...) custom-call(...)``
    -> ``device_copy_with_checksum_chunk``, so that the chunks of one
    program add up under one name."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def top_ops(tr: Trace, chips: Sequence[int], top: int = 10) -> List[list]:
    """Device operations with the most summed time in the window, as
    [name, seconds], by :func:`op_kind`; the chip prefixes the name
    where there are several."""
    lo, hi = tr.window()
    tot: Dict[str, float] = {}
    for c in chips:
        for e in tr.ops.get(c, []):
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                key = op_kind(e.name)
                key = f"TPU_{c} {key}" if len(chips) > 1 else key
                tot[key] = tot.get(key, 0.0) + (t - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def program_time_in_requests(tr: Trace, chips: Sequence[int],
                             pattern: str) -> float:
    """Summed device seconds of programs whose name matches ``pattern``
    and that run inside a request completed in the window."""
    rx = re.compile(pattern)
    reqs = union(completed_requests(tr))
    total = 0.0
    for c in chips:
        evs = [(e.start, e.end) for e in tr.modules.get(c, [])
               if rx.search(e.name)]
        total += measure(intersect(reqs, union(evs)))
    return total / 1e9


# ---- readers of a run (metrics/<name>.py point here) -----------------------

def run_idle_pct(run):
    return None if run.trace is None else idle_pct(run.trace, run.chips)


def run_host_only_us(run):
    if run.trace is None:
        return None
    seconds, n = host_only_s(run.trace, run.chips)
    return seconds / n * 1e6 if n else None
