"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own, and this module finds it from the
names in ``BENCHMARK.json``:

- ``benchmark/configs/<config>.json``  — the deployment (``file`` in
  ``BENCHMARK.json``), whose ``system`` names the adapter below;
- ``benchmark/traffic/<traffic>.json`` — the mix's parameters;
- ``benchmark/systems/<system>.py``    — how to stand the system up and
  drive one request (one per kind of service, not per cell);
- ``benchmark/metrics/<metric>.py``    — one reader per metric (a
  metric split by a suffix, ``<metric>.<group>``, shares its reader).

A later PR adds a cell, a configuration, a mix or a metric by adding
files and entries; no existing file has to change.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    root: str = ""  # the benchmark's directory: configs/, traffic/, ...


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load_cell(name: str, root: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its config,
    its traffic and the metrics that apply to it.  Raises KeyError for
    an unknown cell, FileNotFoundError for a missing file."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.dirname(os.path.dirname(os.path.join(root, cfg_entry["file"])))
    traffic = _load_json(
        os.path.join(bench_dir, "traffic", w["traffic"] + ".json")
    )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=bench_dir,
    )


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def system_module(cell: Cell):
    """The adapter ``systems/<config.system>.py``."""
    sysname = cell.config["system"]
    return _load_module(
        os.path.join(cell.root, "systems", sysname + ".py"),
        f"_bench_system_{sysname}",
    )


def metric_reader(cell: Cell, metric_name: str):
    """``read(run)`` of ``metrics/<metric_name>.py``, or, where no file
    has the whole name, of ``metrics/<base>.py`` for a name split by a
    suffix (``device_idle_pct.bulk`` → ``device_idle_pct.py``): the
    suffix only groups cells that report different end-to-end metrics."""
    base = metric_name
    path = os.path.join(cell.root, "metrics", base + ".py")
    while not os.path.exists(path) and "." in base:
        base = base.rsplit(".", 1)[0]
        path = os.path.join(cell.root, "metrics", base + ".py")
    mod = _load_module(path, "_bench_metric_" + base.replace(".", "_"))
    return mod.read


def read_metrics(cell: Cell, metrics: List[dict], run) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for every metric whose reader finds
    something to read; a reader that returns None is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = metric_reader(cell, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
