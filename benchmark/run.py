#!/usr/bin/env python
"""Run one cell of the benchmark once, on the chip this process holds.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
both are data files found by name (``spec.py``).  The run refuses at
once, printing no result, unless JAX's first device is a TPU and it
sees the chips the cell asks for.  It then stands the system up from
the seed, warms the cell's own shapes, runs every caller in a closed
loop for ``--seconds``, closes the program's state, and checks a seeded
sample of what the window produced against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; last in it, ``checks``: every number compared, with its
limit.  Earlier lines carry the set-up split, the program's sanity
counters, sample counts and the generator's lateness.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)  # the checkout: the program under test
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0 if _p == HERE else 1, _p)

import spec  # noqa: E402
import tracereduce as tracing  # noqa: E402
from generator import run_window  # noqa: E402
from peaks import peaks  # noqa: E402

WARM_CALLS = 3  # per caller, before the window: compiles every shape
TRACE_OFFSET_S = 1.0  # the traced stretch starts this far into the window
TRACE_S = 3.0  # and lasts this long (less in a shorter window)


class NoChip(RuntimeError):
    pass


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc), so that set-up
    counts the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def require_chips(n: int):
    """The first ``n`` TPU devices, or NoChip.  Touches nothing else of
    JAX first."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: the first device is {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    return devices


class CompileClock:
    """Counts JAX backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


@dataclass
class RunView:
    """What a metric reader sees of one run."""

    cell: object
    log: object  # generator.WindowLog
    bench: object  # the system adapter
    setup_s: float
    chips: list  # device ids the cell uses
    trace: object = None  # trace.Trace of the traced stretch, or None
    peaks: dict = None

    @property
    def completed(self) -> int:
        return int(self.log.completed_mask().sum())


def warm(callers, calls: int) -> None:
    """Every caller's first requests, all callers at once (the window's
    concurrency), before the clock starts."""
    import threading

    errs = []

    def go(c):
        for k in range(calls):
            if not c.call(k):
                errs.append(k)

    ths = [threading.Thread(target=go, args=(c,)) for c in callers]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    if errs:
        raise RuntimeError(f"{len(errs)} warm-up request(s) failed")


def traced_window(seconds: float, box: dict):
    """``during`` hook of the traced run: profile a steady stretch of
    the window into a temporary directory."""
    import jax

    def during(t0_ns):
        off = min(TRACE_OFFSET_S, seconds * 0.2)
        length = max(0.2, min(TRACE_S, seconds - off - 0.2))
        delay = t0_ns / 1e9 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        d = tempfile.mkdtemp(prefix="bench-trace-")
        box["dir"] = d
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(length)
        finally:
            jax.profiler.stop_trace()

    return during


def read_trace(box: dict):
    d = box.get("dir")
    if not d:
        return None
    try:
        found = []
        for dirpath, _, files in os.walk(d):
            found += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".xplane.pb")]
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        return tracing.from_profile(sorted(found)[-1])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run_once(cell, devices, seed: int, seconds: float, trace: bool,
             control: bool = False, emit=print, stamps=None) -> dict:
    """Stand the cell up, warm it, run the window, check it.  Returns
    the result object (the last line's).  ``stamps`` holds the process
    ages at which the earlier parts of set-up ended."""
    import jax

    stamps = dict(stamps or {})
    stamps["start"] = process_age_s()
    clock = CompileClock()
    system = spec.system_module(cell)
    bench = system.build(cell, devices, seed, control=control)
    stamps["load"] = process_age_s()
    c0 = (clock.count, clock.seconds)
    warm(bench.callers, WARM_CALLS)
    for c in bench.callers:  # warm-up answers are not the window's
        c.sample.clear()
    before = bench.counters()
    compiles_before = clock.count
    setup_s = process_age_s()
    stamps["warm"] = setup_s
    box: dict = {}
    log = run_window(
        bench.callers, seconds,
        annotate=jax.profiler.TraceAnnotation if trace else None,
        during=traced_window(seconds, box) if trace else None,
    )
    window_compiles = clock.count - compiles_before
    used = [devices[i] for i in bench.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    after = bench.counters()
    bench.close()
    checks = bench.check(log.failed)
    parts = checks.pop("parts")
    tr = read_trace(box) if trace else None
    kind = devices[0].device_kind
    view = RunView(cell=cell, log=log, bench=bench, setup_s=setup_s,
                   chips=[d.id for d in used], trace=tr,
                   peaks=peaks(kind) if devices[0].platform == "tpu" else None)
    metrics = spec.read_metrics(
        cell, cell.per_layer if trace else cell.end_to_end, view)
    lat = log.between_ns
    split = {}
    prev = 0.0
    for name in ("imports", "tpu_init", "program_import", "start", "load",
                 "warm"):
        if name in stamps:
            if name != "start":
                split[name + "_s"] = stamps[name] - prev
            prev = stamps[name]
    emit(json.dumps({
        "setup": {**split, "total_s": setup_s,
                  "warm_compiles": clock.count - c0[0],
                  "warm_compile_s": clock.seconds - c0[1]},
        "window": {"seconds": log.window_s, "completed": view.completed,
                   "compiles_in_window": window_compiles,
                   "between_us_p50": (sorted(lat)[len(lat) // 2] / 1e3
                                      if lat else None),
                   "between_us_max": max(lat) / 1e3 if lat else None},
        "counters": {k: after[k] - before.get(k, 0) for k in after},
        "wrong_answer_parts": parts,
    }))
    ok = all(
        (c["value"] >= c["limit"]) if c.get("at_least") else
        (c["value"] <= c["limit"])
        for c in checks.values()
    )
    result = {
        "correct": ok,
        "attempted": log.attempted,
        "failed": log.failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if tr is not None:
        result["device"]["busy_s"] = tracing.busy_s(tr, view.chips)
        result["device"]["window_s"] = tracing.window_s(tr)
        result["breakdown"] = {
            "device_ops": tracing.top_ops(tr, view.chips),
            "idle_gaps": tracing.idle_gaps(tr, view.chips),
        }
        if len(view.chips) > 1:
            emit(json.dumps({"idle_pct_per_chip": {
                f"TPU_{c}": tracing.idle_pct(tr, [c]) for c in view.chips}}))
    result["checks"] = checks
    return result


def main(argv=None, chips=require_chips, root=ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    import jax

    stamps = {"imports": process_age_s()}
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    stamps["tpu_init"] = process_age_s()
    try:
        from incubator_brpc_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"run.py: the program is not here: {e}", file=sys.stderr)
        return 3
    stamps["program_import"] = process_age_s()

    cache = enable_compile_cache()
    # every program, however quick to compile, comes from the cache
    # after the first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "compile_cache": cache}), flush=True)

    def emit(line):
        print(line, flush=True)

    result = run_once(cell, devices, args.seed, args.seconds,
                      bool(args.trace), emit=emit, stamps=stamps)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
