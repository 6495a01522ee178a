"""Helpers of the benchmark's tests: a copy of the benchmark at tiny
sizes, and a run of it in this process with the look for a chip
skipped."""

from __future__ import annotations

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# two callers, not eight: the mix's concurrency at a load that leaves the
# other test workers of an `-n 6` run their cores
TINY_TRAFFIC = {"bulk64m": {"shape": [256, 256]},
                "small4k": {"callers_per_server": 2},
                "b": {"sample": 512, "callers_per_server": 2}}
TINY_CONFIG = {"ycsb_1kb": {"recordcount": 300}}


def _patch_json(path, changes):
    with open(path) as f:
        d = json.load(f)
    d.update(changes)
    with open(path, "w") as f:
        json.dump(d, f)


def make_copy(dest: str) -> str:
    """BENCHMARK.json and benchmark/ under ``dest``, at tiny sizes."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(BENCH, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, ch in TINY_TRAFFIC.items():
        _patch_json(os.path.join(dest, "benchmark", "traffic", name + ".json"), ch)
    for name, ch in TINY_CONFIG.items():
        _patch_json(os.path.join(dest, "benchmark", "configs", name + ".json"), ch)
    return dest


def cpu_chips(n):
    import jax

    return jax.devices()[:n]


def run_cell(root, workload, seed=12345, seconds=0.5, trace=0):
    """run.main on the CPU (the chip check skipped): (rc, stdout lines,
    the last line's object)."""
    import run

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      chips=cpu_chips, root=root)
    lines = buf.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


