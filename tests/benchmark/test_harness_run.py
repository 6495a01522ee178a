"""run.py as the driver starts it: refusal without a chip or without
the program, the peaks table, and cells found by name in data files."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO, make_copy, run_cell


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "echo_1chip.bulk64m",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files:
    past the chip check (skipped here), the program is not found."""
    make_copy(str(tmp_path))
    code = (
        "import sys, jax; sys.path[:0] = ['benchmark']; import run; "
        "sys.exit(run.main(['--workload', 'echo_1chip.bulk64m', '--seed', '1', "
        "'--seconds', '1'], chips=lambda n: jax.devices()[:n]))"
    )
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_unknown_device_kind_raises():
    from peaks import UnknownDevice, peaks

    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("TPU v99 imaginary")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_cell_config_mix_and_metric_are_added_as_files(tmp_path):
    """Data-driven: a new configuration, traffic mix, metric and cell
    are new files and new entries; no existing file is edited."""
    root = make_copy(str(tmp_path))
    bench_dir = os.path.join(root, "benchmark")
    before = _digest(bench_dir)
    with open(os.path.join(bench_dir, "configs", "echo_1chip.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "echo_extra"
    with open(os.path.join(bench_dir, "configs", "echo_extra.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench_dir, "traffic", "mid64k.json"), "w") as f:
        json.dump({"kind": "closed_loop_echo", "shape": [128, 128],
                   "dtype": "float32", "pool": 2, "callers_per_server": 2,
                   "sample": 8}, f)
    with open(os.path.join(bench_dir, "metrics", "lat_p50_ms.py"), "w") as f:
        f.write("from stats import percentile\n\n\ndef read(run):\n"
                "    lat = run.log.latencies_ms()\n"
                "    return percentile(lat, 50) if lat else None\n")
    bj = os.path.join(root, "BENCHMARK.json")
    with open(bj) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "echo_extra", "source": "https://example.org",
                             "file": "benchmark/configs/echo_extra.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "echo_extra.mid64k", "config": "echo_extra",
                               "traffic": "mid64k", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "lat_p50_ms", "unit": "ms", "better": "lower",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["echo_extra.mid64k"]})
    with open(bj, "w") as f:
        json.dump(bench, f)
    rc, _, res = run_cell(root, "echo_extra.mid64k")
    assert rc == 0 and res["correct"] is True
    assert {"lat_p50_ms", "lat_p99_ms", "setup_s"} <= set(res["metrics"])
    after = _digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/echo_extra.json", "traffic/mid64k.json", "metrics/lat_p50_ms.py"}


def _metric_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]


@pytest.mark.parametrize("metric", _metric_names())
def test_every_metric_has_a_reader(metric):
    """Each metric of BENCHMARK.json is found by name: its own file, or
    for ``<base>.<group>`` the one file of its base."""
    import spec

    cell = spec.Cell(name="-", chips=1, config={}, traffic={},
                     root=os.path.join(REPO, "benchmark"))
    assert callable(spec.metric_reader(cell, metric))


def test_a_metric_group_shares_its_base_reader(tmp_path):
    """A new suffix needs no copied file; a file of the whole name still
    takes precedence over the base."""
    import spec

    root = make_copy(str(tmp_path))
    metrics = os.path.join(root, "benchmark", "metrics")
    cell = spec.Cell(name="-", chips=1, config={}, traffic={},
                     root=os.path.join(root, "benchmark"))
    base = spec.metric_reader(cell, "host_only_us")
    assert spec.metric_reader(cell, "host_only_us.newgroup") is base
    with open(os.path.join(metrics, "host_only_us.newgroup.py"), "w") as f:
        f.write("def read(run):\n    return 1.0\n")
    assert spec.metric_reader(cell, "host_only_us.newgroup")(None) == 1.0
    with pytest.raises(FileNotFoundError):
        spec.metric_reader(cell, "no_such_metric.small")
