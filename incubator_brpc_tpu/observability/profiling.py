"""Device-plane continuous profiling — HBM heap, device time, occupancy.

The host half of the reference's /hotspots suite (cpu/contention/heap/
growth) says nothing about the device plane: which subsystem pins how
much HBM, where device time goes, and whether the runtime's workers
are actually busy.  This module holds the three profilers that answer
those questions, each in the house shape — cheap always-on counters, an
on-demand deep capture, and a loud cross-check instead of a trusted
registry:

1. **HBM heap profiler** — ``hbm_account(tag)`` hands out a per-tag
   accounting handle every HBM-pinning subsystem adopts: the cache
   store's values and gather pads, StagingRing slots, sharded PS
   params, decode row state, in-flight ICI DeviceRefs.  Adopted bytes
   aggregate into ``rpc_hbm_bytes{component}``; /hotspots/hbm renders
   the per-tag profile and cross-checks the ledger against the
   device's own census (``device.memory_stats()`` where the backend
   provides it, a ``jax.live_arrays()`` walk otherwise) so bytes the
   registry does not know about surface as an explicit ``<dark>``
   bucket — a ledger drifting from reality fails loudly, it never lies.

2. **Kernel-family attribution** — kernel dispatch sites (FusedKernel,
   the sharded collective, decode step, ICI chunk pipeline, PS
   forward) wrap their dispatch in :class:`kernel_section`, feeding
   per-family execution counts and host-clock dispatch-time EMAs
   (``rpc_kernel_dispatch_us_*``: the host's time around a dispatch
   that is not synchronised, so not device time).  No section adds a
   ``block_until_ready`` to a hot path, so the transfer witness stays
   green.  ``/hotspots/device?seconds=N`` arms an on-demand
   ``jax.profiler.trace`` window: each family's device time there is
   read from the XLA Modules events of that trace, linked to the
   section that dispatched them, and the window's rpcz spans are
   written beside the ``.xplane.pb`` on the trace's clock.

3. **Runtime occupancy sampler** — per-worker run-queue depth, steals,
   runs, parks and task queue-wait from runtime/scheduler's plain
   counters, exported as ``rpc_worker_*`` gauges and /hotspots/runtime
   (the occupancy evidence the M:N-scheduler roadmap item cites).

This module must import WITHOUT jax (it is render-checked by the
``metrics-unrenderable`` lint): every jax touch goes through
``sys.modules.get("jax")`` — if jax was never imported, no HBM exists
to account for.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
import tempfile
import threading
import time
import warnings
from typing import Dict, List, Optional

from incubator_brpc_tpu.metrics.multi_dimension import MultiDimension
from incubator_brpc_tpu.metrics.passive_status import PassiveStatus, Status
from incubator_brpc_tpu.metrics.reducer import Adder
from incubator_brpc_tpu.observability import span as _span
from incubator_brpc_tpu.runtime import scheduler as _sched
from incubator_brpc_tpu.utils.flags import define_flag

# ---------------------------------------------------------------------------
# gates — the always-on halves are flag-gated so the OFF/ON/OFF overhead
# bench (and an operator chasing a regression) can kill them at runtime
# ---------------------------------------------------------------------------

_HBM_FLAG = define_flag(
    "profiler_hbm_enabled",
    True,
    "always-on HBM accounting (rpc_hbm_bytes / /hotspots/hbm)",
    validator=lambda v: isinstance(v, bool),
)
_DEVICE_FLAG = define_flag(
    "profiler_device_enabled",
    True,
    "always-on per-kernel-family dispatch counts and host dispatch time",
    validator=lambda v: isinstance(v, bool),
)
_OCC_FLAG = define_flag(
    "profiler_occupancy_enabled",
    True,
    "runtime occupancy sampling (rpc_worker_* / /hotspots/runtime)",
    validator=lambda v: isinstance(v, bool),
)

# ---------------------------------------------------------------------------
# (1) HBM heap profiler
# ---------------------------------------------------------------------------

#: live device bytes / allocation counts per accounting tag
rpc_hbm_bytes = MultiDimension(Adder, ["component"]).expose("rpc_hbm_bytes")
rpc_hbm_allocs = MultiDimension(Adder, ["component"]).expose("rpc_hbm_allocs")


class HbmAccount:
    """Per-tag accounting handle.  The contract every adopter follows:

    - ``n = acct.adopt(arr_or_nbytes)`` when a device buffer becomes
      this subsystem's responsibility (returns the bytes charged —
      store it);
    - ``acct.release(n)`` with exactly that stored value when the
      buffer is freed, donated away, or handed to another account.

    Storing adopt's return (instead of re-reading ``.nbytes`` at
    release) keeps the ledger balanced even across runtime gate flips.
    Reading ``.nbytes`` off a jax array is metadata only — no device
    transfer, so adoption is witness-safe on any path.
    """

    __slots__ = ("tag", "_bytes", "_allocs")

    def __init__(self, tag: str):
        self.tag = tag
        self._bytes = rpc_hbm_bytes.get_stats([tag])
        self._allocs = rpc_hbm_allocs.get_stats([tag])

    def adopt(self, obj) -> int:
        if not _HBM_FLAG.value:
            return 0
        n = obj if isinstance(obj, int) else int(getattr(obj, "nbytes", 0) or 0)
        if n > 0:
            self._bytes << n
            self._allocs << 1
        return n

    def release(self, nbytes: int, allocs: int = 1) -> None:
        if nbytes > 0:
            self._bytes << -int(nbytes)
            self._allocs << -int(allocs)

    def live_bytes(self) -> int:
        return int(self._bytes.get_value())

    def live_allocs(self) -> int:
        return int(self._allocs.get_value())


_accounts: Dict[str, HbmAccount] = {}
_accounts_lock = threading.Lock()


def hbm_account(tag: str) -> HbmAccount:
    """The one entry point: register (first call) or look up the
    accounting handle for ``tag``."""
    acct = _accounts.get(tag)
    if acct is None:
        with _accounts_lock:
            acct = _accounts.get(tag)
            if acct is None:
                acct = HbmAccount(tag)
                _accounts[tag] = acct
    return acct


def device_census() -> dict:
    """The device's own notion of live bytes, for the ``<dark>``
    cross-check.  Prefers ``device.memory_stats()`` (real allocator
    numbers on TPU/GPU); falls back to summing ``.nbytes`` over
    ``jax.live_arrays()`` (CPU backend has no allocator stats).  Both
    reads are metadata-only — no device→host transfer."""
    jax = sys.modules.get("jax")
    if jax is None:
        return {
            "available": False,
            "source": None,
            "bytes": 0,
            "reason": "jax not loaded (nothing on the device)",
        }
    try:
        total, got = 0, False
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            if ms is None:
                continue
            try:
                stats = ms()
            except Exception:  # noqa: BLE001 — backend without stats
                stats = None
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                got = True
        if got:
            return {"available": True, "source": "memory_stats", "bytes": total}
    except Exception:  # noqa: BLE001 — fall through to the array walk
        pass
    try:
        total = sum(int(a.nbytes) for a in jax.live_arrays())
        return {"available": True, "source": "live_arrays", "bytes": total}
    except Exception as e:  # noqa: BLE001
        return {
            "available": False,
            "source": None,
            "bytes": 0,
            "reason": repr(e),
        }


# census baseline: device bytes that predate the accounting horizon
# (compiled executables' constants, weights loaded before adoption
# began).  dark = census - baseline - accounted; rebase_census() snaps
# the horizon "everything currently resident is explained".
_census_baseline = [0]


def rebase_census() -> dict:
    cen = device_census()
    _census_baseline[0] = cen["bytes"] if cen["available"] else 0
    return cen


def hbm_profile() -> dict:
    """Ledger snapshot + census cross-check (the /hotspots/hbm data)."""
    tags: Dict[str, dict] = {}
    with _accounts_lock:
        accounts = list(_accounts.values())
    for acct in accounts:
        b, a = acct.live_bytes(), acct.live_allocs()
        if b or a:
            tags[acct.tag] = {"bytes": b, "allocs": a}
    accounted = sum(v["bytes"] for v in tags.values())
    cen = device_census()
    dark: Optional[int] = None
    if cen["available"]:
        dark = max(0, cen["bytes"] - _census_baseline[0] - accounted)
    return {
        "tags": tags,
        "accounted_bytes": accounted,
        "census": cen,
        "census_baseline": _census_baseline[0],
        "dark_bytes": dark,
    }


def render_hbm(profile: Optional[dict] = None, top: int = 40) -> str:
    """pprof-style text profile: hottest tag first, then the census
    cross-check with the explicit ``<dark>`` bucket."""
    p = profile if profile is not None else hbm_profile()
    cen = p["census"]
    out = [
        "--- hbm",
        f"accounted_bytes: {p['accounted_bytes']}  tags: {len(p['tags'])}",
    ]
    if cen["available"]:
        out.append(
            f"census: source={cen['source']} bytes={cen['bytes']} "
            f"baseline={p['census_baseline']}"
        )
        dark = p["dark_bytes"]
        span = max(1, cen["bytes"] - p["census_baseline"])
        out.append(f"<dark>: {dark} bytes ({100.0 * dark / span:.1f}%)")
    else:
        out.append(f"census: unavailable ({cen.get('reason')}) — <dark> unknown")
    out.append("")
    rows = sorted(
        p["tags"].items(), key=lambda kv: kv[1]["bytes"], reverse=True
    )[:top]
    for tag, row in rows:
        out.append(f"{row['bytes']:>14} {row['allocs']:>8} @ {tag}")
    return "\n".join(out)


# growth baseline slot (same idiom as /hotspots/growth's tracemalloc
# slot): each fetch diffs against the previous one
_hbm_growth_baseline: list = [None]


def render_hbm_growth(top: int = 40) -> str:
    p = hbm_profile()
    base = _hbm_growth_baseline[0]
    _hbm_growth_baseline[0] = p
    if base is None:
        return "hbm baseline captured; re-fetch for growth"
    out = ["--- hbm growth since last fetch", ""]
    deltas = []
    for tag in sorted(set(p["tags"]) | set(base["tags"])):
        nb = p["tags"].get(tag, {}).get("bytes", 0)
        ob = base["tags"].get(tag, {}).get("bytes", 0)
        na = p["tags"].get(tag, {}).get("allocs", 0)
        oa = base["tags"].get(tag, {}).get("allocs", 0)
        if nb != ob or na != oa:
            deltas.append((nb - ob, na - oa, tag))
    deltas.sort(key=lambda t: abs(t[0]), reverse=True)
    for db, da, tag in deltas[:top]:
        out.append(f"{db:>+14} {da:>+8} @ {tag}")
    if len(out) == 2:
        out.append("(no per-tag change)")
    out.append("")
    out.append(
        f"accounted: {base['accounted_bytes']} -> {p['accounted_bytes']} "
        f"({p['accounted_bytes'] - base['accounted_bytes']:+d})"
    )
    if p["census"]["available"] and base["census"]["available"]:
        out.append(
            f"census:    {base['census']['bytes']} -> {p['census']['bytes']} "
            f"({p['census']['bytes'] - base['census']['bytes']:+d})"
        )
    return "\n".join(out)


# ---------------------------------------------------------------------------
# (2) kernel-family attribution
# ---------------------------------------------------------------------------

rpc_kernel_executions = MultiDimension(Adder, ["family"]).expose(
    "rpc_kernel_executions"
)
rpc_kernel_dispatch_us_total = MultiDimension(Adder, ["family"]).expose(
    "rpc_kernel_dispatch_us_total"
)
rpc_kernel_dispatch_us_ema = MultiDimension(
    lambda: Status(0.0), ["family"]
).expose("rpc_kernel_dispatch_us_ema")

_EMA_ALPHA = 0.2


class _KernelStat:
    __slots__ = ("family", "_exec", "_total", "_ema_var", "ema_us", "last_us")

    def __init__(self, family: str):
        self.family = family
        self._exec = rpc_kernel_executions.get_stats([family])
        self._total = rpc_kernel_dispatch_us_total.get_stats([family])
        self._ema_var = rpc_kernel_dispatch_us_ema.get_stats([family])
        self.ema_us: Optional[float] = None
        self.last_us = 0.0

    def note(self, us: float) -> None:
        self._exec << 1
        self._total << us
        self.last_us = us
        ema = self.ema_us
        self.ema_us = us if ema is None else ema + _EMA_ALPHA * (us - ema)
        self._ema_var.set_value(round(self.ema_us, 2))


_kernels: Dict[str, _KernelStat] = {}
_kernels_lock = threading.Lock()


def _kernel_stat(family: str) -> _KernelStat:
    st = _kernels.get(family)
    if st is None:
        # construct OUTSIDE the lock (variable registration walks the
        # metrics registry); setdefault keeps first-registration unique
        fresh = _KernelStat(family)
        with _kernels_lock:
            st = _kernels.setdefault(family, fresh)
    return st


# host TraceMe around a section while a profiler session records: the
# programs launched inside it are charged to its family (device_capture)
SECTION_PREFIX = "kernel_section:"


class kernel_section:
    """Counts one kernel-family dispatch and times it on the host clock
    (the dispatch, not the device work: nothing here syncs the device).
    Disarmed cost is one flag load and one profiler-session check;
    armed, two perf_counter reads plus the counter folds.  While a
    profiler session records, the section is also a host trace event
    (``kernel_section:<family>``) that device_capture links the
    device's programs to."""

    __slots__ = ("family", "_t0", "_tm")

    def __init__(self, family: str):
        self.family = family
        self._t0 = 0
        self._tm = None

    def __enter__(self) -> "kernel_section":
        if _DEVICE_FLAG.value:
            self._t0 = time.perf_counter_ns()
        if _span._profiler_on():
            self._tm = sys.modules["jaxlib._profiler"].TraceMe(
                SECTION_PREFIX + self.family
            )
            self._tm.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._tm is not None:
            self._tm.__exit__(exc_type, exc, tb)
        if self._t0 and exc_type is None:
            _kernel_stat(self.family).note(
                (time.perf_counter_ns() - self._t0) / 1000.0
            )
        return False


def kernel_snapshot() -> Dict[str, dict]:
    """family → {executions, total_us, ema_us, last_us} (capture diffs
    and the /hotspots/device table read this)."""
    with _kernels_lock:
        stats = list(_kernels.values())
    out: Dict[str, dict] = {}
    for st in stats:
        out[st.family] = {
            "executions": int(st._exec.get_value()),
            "total_us": float(st._total.get_value()),
            "ema_us": round(st.ema_us, 2) if st.ema_us is not None else 0.0,
            "last_us": round(st.last_us, 2),
        }
    return out


def render_device(snapshot: Optional[Dict[str, dict]] = None) -> str:
    """The always-on table: host dispatch time per family (device time
    comes only from a deep capture, ``?seconds=N``)."""
    snap = snapshot if snapshot is not None else kernel_snapshot()
    out = [
        "--- device",
        f"kernel_families: {len(snap)}",
        "host dispatch time (device time: ?seconds=N)",
        "",
        f"{'executions':>12} {'dispatch_us':>14} {'ema_us':>10} "
        f"{'last_us':>10}  family",
    ]
    for family, row in sorted(
        snap.items(), key=lambda kv: kv[1]["total_us"], reverse=True
    ):
        out.append(
            f"{row['executions']:>12} {row['total_us']:>14.1f} "
            f"{row['ema_us']:>10.1f} {row['last_us']:>10.1f}  {family}"
        )
    return "\n".join(out)


# ---- on-demand deep capture ------------------------------------------------

rpc_profiler_captures_total = Adder(0).expose("rpc_profiler_captures_total")
rpc_profiler_capture_failures_total = Adder(0).expose(
    "rpc_profiler_capture_failures_total"
)

_capture_lock = threading.Lock()
_trace_active = [False]
MAX_CAPTURE_SECONDS = 10.0


class CaptureError(RuntimeError):
    """A deep capture that could not run (chaos drop, concurrent
    capture, profiler failure).  The page maps it to an error response;
    serving continues and no armed trace session survives it."""


def capture_active() -> bool:
    return _trace_active[0]


OUTSIDE_SECTIONS = "<outside any section>"


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        # jaxlib's stat iterator type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in ev.stats}


def device_us_by_family(pd) -> Optional[Dict[str, float]]:
    """Device microseconds of a profile's XLA Modules events, per
    kernel family; None when the profile holds no device program (a
    CPU backend).

    A launch is followed from the ``kernel_section:<family>`` host event
    that encloses it: every event nested in a reached event on its line
    is reached, and so is every event whose flow-consumer id (``_c``)
    is a reached event's flow-producer id (``_p``) — on a TPU, the
    Python thread's execute linkage → the PJRT execute → the enqueue on
    a ``pjrt-tpu-tasks`` thread.  A device program whose ``_c`` or
    ``run_id`` a reached event carries is charged to the family; any
    other to ``OUTSIDE_SECTIONS``."""
    lines = []  # per host line: events sorted by start, their starts
    consumers: Dict[object, List[tuple]] = {}
    seeds = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                ((e.start_ns, e.start_ns + e.duration_ns, e.name, _stats(e))
                 for e in line.events), key=lambda x: x[0])
            li = len(lines)
            lines.append((evs, [x[0] for x in evs]))
            for ei, (_, _, name, st) in enumerate(evs):
                if "_c" in st:
                    consumers.setdefault(st["_c"], []).append((li, ei))
                if name.startswith(SECTION_PREFIX):
                    seeds.append((li, ei, name[len(SECTION_PREFIX):]))
    owner: Dict[tuple, str] = {}  # ("_c" | "run_id", value) -> family
    seen = set()
    frontier = list(seeds)
    while frontier:
        li, ei, fam = frontier.pop()
        if (li, ei) in seen:
            continue
        seen.add((li, ei))
        evs, starts = lines[li]
        t0, t1 = evs[ei][0], evs[ei][1]
        j = bisect.bisect_left(starts, t0)
        while j < len(evs) and evs[j][0] <= t1:
            if evs[j][1] <= t1:
                st = evs[j][3]
                if "run_id" in st:
                    owner.setdefault(("run_id", st["run_id"]), fam)
                if "_p" in st:
                    owner.setdefault(("_c", st["_p"]), fam)
                    for lk, ek in consumers.get(st["_p"], ()):
                        frontier.append((lk, ek, fam))
            j += 1
    out: Dict[str, float] = {}
    found = False
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                found = True
                st = _stats(e)
                fam = (owner.get(("_c", st.get("_c")))
                       or owner.get(("run_id", st.get("run_id")))
                       or OUTSIDE_SECTIONS)
                out[fam] = out.get(fam, 0.0) + e.duration_ns / 1000.0
    return out if found else None


def profile_start_ns(pd) -> Optional[int]:
    """The profile's wall-clock start (its events' offsets are from
    it): the rpcz span clock times 1000."""
    for plane in pd.planes:
        v = _stats(plane).get("profile_start_time")
        if v is not None:
            return int(v)
    return None


def _newest_xplane(trace_dir: str) -> Optional[str]:
    found = []
    for d, _, files in os.walk(trace_dir):
        found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def write_capture_spans(path: str, cap, base_ns: Optional[int]) -> None:
    """The capture's spans as JSON (the /rpcz/export span form), each
    with ``start_offset_ns`` from the profile's start when known."""
    from incubator_brpc_tpu.observability.cluster import span_to_dict

    spans = []
    for sp in cap.spans:
        d = span_to_dict(sp)
        if base_ns is not None:
            d["start_offset_ns"] = sp.start_us * 1000 - base_ns
        spans.append(d)
    with open(path, "w") as f:
        json.dump({
            "profile_start_time_ns": base_ns,
            "capture": {"start_us": cap.start_us, "stop_us": cap.stop_us,
                        "overflow": cap.overflow},
            "spans": spans,
        }, f)


def device_capture(seconds: float) -> dict:
    """Arm a ``jax.profiler.trace`` window for ``seconds`` and return a
    per-kernel-family summary of what executed inside it: dispatches
    and host dispatch time from the counters, device time from the
    trace's XLA Modules events (None where the trace has none), and the
    rpcz spans the window captured (also written as ``rpcz_spans.json``
    beside the ``.xplane.pb``).  The chaos site ``profile.capture`` sits
    on this path: ``drop`` fails the capture (CaptureError → error
    page), ``delay_us`` stretches its start.  The trace session is
    disarmed in a ``finally`` — a failed or chaos-faulted capture can
    never leak an armed profiler."""
    from incubator_brpc_tpu.chaos import injector as _chaos

    seconds = min(max(float(seconds), 0.0), MAX_CAPTURE_SECONDS)
    if _chaos.armed:
        spec = _chaos.check("profile.capture")
        if spec is not None:
            if spec.action == "delay_us":
                _chaos.sleep_us(spec.arg)
            elif spec.action == "drop":
                rpc_profiler_capture_failures_total << 1
                raise CaptureError(
                    "deep capture dropped (chaos site profile.capture)"
                )
    if not _capture_lock.acquire(blocking=False):
        raise CaptureError("a device capture is already in progress")
    try:
        before = kernel_snapshot()
        t0 = time.perf_counter()
        t0_us = time.time_ns() // 1000
        jax = sys.modules.get("jax")
        trace_dir: Optional[str] = None
        trace_error: Optional[str] = None
        started = False
        if jax is not None:
            try:
                trace_dir = tempfile.mkdtemp(prefix="device-trace-")
                jax.profiler.start_trace(trace_dir)
                started = True
                _trace_active[0] = True
            except Exception as e:  # noqa: BLE001 — degrade to counters-only
                trace_error = repr(e)
                trace_dir = None
        else:
            trace_error = "jax not loaded"
        try:
            time.sleep(seconds)
        finally:
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:  # noqa: BLE001
                    trace_error = trace_error or repr(e)
                _trace_active[0] = False
        after = kernel_snapshot()
        cap = _span.last_capture()
        if cap is not None and cap.start_us < t0_us:
            cap = None  # no span was created inside this window
        device: Optional[Dict[str, float]] = None
        spans_file: Optional[str] = None
        xplane = _newest_xplane(trace_dir) if trace_dir else None
        if xplane is not None:
            try:
                pd = jax.profiler.ProfileData.from_file(xplane)
                device = device_us_by_family(pd)
                if cap is not None:
                    spans_file = os.path.join(
                        os.path.dirname(xplane), "rpcz_spans.json")
                    write_capture_spans(spans_file, cap, profile_start_ns(pd))
            except Exception as e:  # noqa: BLE001 — the counters still stand
                trace_error = trace_error or repr(e)
        rpc_profiler_captures_total << 1
        families: Dict[str, dict] = {}
        for family, row in after.items():
            prev = before.get(family, {"executions": 0, "total_us": 0.0})
            d_exec = row["executions"] - prev["executions"]
            if d_exec <= 0:
                continue
            families[family] = {
                "executions": d_exec,
                "dispatch_us": round(row["total_us"] - prev["total_us"], 1),
                "device_us": (round(device.get(family, 0.0), 1)
                              if device is not None else None),
                "ema_us": row["ema_us"],
            }
        return {
            "seconds": round(time.perf_counter() - t0, 3),
            "families": families,
            "device_outside_sections_us": (
                round(device.get(OUTSIDE_SECTIONS, 0.0), 1)
                if device is not None else None),
            "spans": list(cap.spans) if cap is not None else [],
            "spans_overflow": cap.overflow if cap is not None else 0,
            "spans_file": spans_file,
            "trace_dir": trace_dir,
            "trace_error": trace_error,
        }
    finally:
        _capture_lock.release()


def render_capture(result: dict) -> str:
    out = [
        "--- device capture",
        f"window_s: {result['seconds']}",
        f"trace_dir: {result['trace_dir'] or '(none)'}",
        f"rpcz spans: {len(result['spans'])} "
        f"(overflow {result['spans_overflow']}) "
        f"in {result['spans_file'] or '(none)'}",
    ]
    if result["trace_error"]:
        out.append(f"trace: unavailable ({result['trace_error']}) — "
                   f"summary is counter-based")
    if result["device_outside_sections_us"] is None:
        out.append("device_us: n/a (the trace holds no device program)")
    else:
        out.append(f"device_us outside any section: "
                   f"{result['device_outside_sections_us']:.1f}")
    out.append("")
    out.append(f"{'executions':>12} {'device_us':>14} {'dispatch_us':>14} "
               f"{'ema_us':>10}  family")
    for family, row in sorted(
        result["families"].items(),
        key=lambda kv: (kv[1]["device_us"] or 0.0, kv[1]["dispatch_us"]),
        reverse=True,
    ):
        dev = row["device_us"]
        dev_s = f"{dev:>14.1f}" if dev is not None else f"{'n/a':>14}"
        out.append(
            f"{row['executions']:>12} {dev_s} {row['dispatch_us']:>14.1f} "
            f"{row['ema_us']:>10.1f}  {family}"
        )
    if not result["families"]:
        out.append("(no kernel dispatches inside the window)")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# (3) runtime occupancy sampler
# ---------------------------------------------------------------------------

# queue-wait aggregate fed by the scheduler's occupancy observer slot.
# Plain dict slots mutated under the GIL — a lost update under extreme
# contention costs one sample, never correctness.
_queue_wait = {"count": 0, "total_us": 0, "ema_us": 0.0}


def _occupancy_cb(wait_us: int) -> None:
    _queue_wait["count"] += 1
    _queue_wait["total_us"] += wait_us
    ema = _queue_wait["ema_us"]
    _queue_wait["ema_us"] = (
        float(wait_us) if not ema else ema + _EMA_ALPHA * (wait_us - ema)
    )


def _ctl():
    # never get_task_control(): a metrics render must not be what spawns
    # the worker pool
    return _sched._default_control


def occupancy_snapshot() -> dict:
    ctl = _ctl()
    base = (
        ctl.occupancy_snapshot()
        if ctl is not None
        else {
            "workers": 0,
            "blocked": 0,
            "parked": 0,
            "parks_total": 0,
            "steals_total": 0,
            "remote_q": 0,
            "per_worker": [],
        }
    )
    base["queue_wait"] = {
        "count": _queue_wait["count"],
        "total_us": _queue_wait["total_us"],
        "ema_us": round(_queue_wait["ema_us"], 1),
    }
    return base


def render_runtime(snapshot: Optional[dict] = None) -> str:
    s = snapshot if snapshot is not None else occupancy_snapshot()
    qw = s["queue_wait"]
    out = [
        "--- runtime occupancy",
        f"workers: {s['workers']}  blocked: {s['blocked']}  "
        f"parked: {s['parked']}",
        f"steals_total: {s['steals_total']}  parks_total: {s['parks_total']}  "
        f"remote_q: {s['remote_q']}",
        f"queue_wait: count={qw['count']} total_us={qw['total_us']} "
        f"ema_us={qw['ema_us']}",
        "",
        f"{'worker':>8} {'rq_depth':>10} {'steals':>8} {'runs':>10}",
    ]
    for w in s["per_worker"]:
        out.append(
            f"{w['worker_id']:>8} {w['rq_depth']:>10} {w['steals']:>8} "
            f"{w['runs']:>10}"
        )
    if not s["per_worker"]:
        out.append("(runtime not started)")
    return "\n".join(out)


# worker gauges: PassiveStatus over the (maybe not yet created) default
# control — 0 before the runtime starts, live numbers after
rpc_worker_count = PassiveStatus(
    lambda: _ctl().worker_count() if _ctl() else 0
).expose("rpc_worker_count")
rpc_worker_blocked = PassiveStatus(
    lambda: _ctl().blocked_count() if _ctl() else 0
).expose("rpc_worker_blocked")
rpc_worker_parked = PassiveStatus(
    lambda: _ctl().parked_count() if _ctl() else 0
).expose("rpc_worker_parked")
rpc_worker_parks_total = PassiveStatus(
    lambda: _ctl().parks_total() if _ctl() else 0
).expose("rpc_worker_parks_total")
rpc_worker_steals_total = PassiveStatus(
    lambda: _ctl().steals_total() if _ctl() else 0
).expose("rpc_worker_steals_total")
rpc_worker_runqueue_depth = PassiveStatus(
    lambda: _ctl().runqueue_depth() if _ctl() else 0
).expose("rpc_worker_runqueue_depth")
rpc_worker_queue_waits_total = PassiveStatus(
    lambda: _queue_wait["count"]
).expose("rpc_worker_queue_waits_total")
rpc_worker_queue_wait_us_ema = PassiveStatus(
    lambda: round(_queue_wait["ema_us"], 1)
).expose("rpc_worker_queue_wait_us_ema")

# arm the sampler: the scheduler stamps queue-in times only while an
# observer's gate is open, so flipping profiler_occupancy_enabled off
# removes even the per-spawn clock read (unless rpcz wants it too)
_sched.set_occupancy_observer(_occupancy_cb, gate=_OCC_FLAG)
