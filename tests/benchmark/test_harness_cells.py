"""Each cell's mix at a tiny size on the CPU, the transmit kernels
through the Pallas interpreter: the whole run, as the chip runs it."""

from __future__ import annotations

import json

import pytest

from bench_helpers import run_cell

CONTRACT = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", [
    "echo_1chip.bulk64m", "ycsb_1kb.b", "echo_1chip.small4k",
])
def test_cell_runs_correct(tiny, interpret_kernels, workload):
    import spec

    rc, lines, res = run_cell(tiny, workload)
    assert rc == 0
    assert list(res) == CONTRACT  # only the contract's keys, checks last
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = spec.load_cell(workload, tiny)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert res["device"]["count"] >= cell.chips
    counters = next(json.loads(x) for x in lines if '"counters"' in x)["counters"]
    if workload.startswith("echo_"):
        # every same-chip segment went through a transmit kernel
        assert counters["rpc_ici_unchecked_segments"] == 0, counters


def test_traced_run_carries_breakdown(tiny, interpret_kernels):
    rc, _, res = run_cell(tiny, "echo_1chip.small4k", seconds=1.5, trace=1)
    assert rc == 0 and res["correct"] is True
    assert list(res) == CONTRACT[:5] + ["breakdown", "checks"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in res["device"] and "busy_s" in res["device"]
    # the CPU has no TPU plane: the host-only share is still read
    assert "host_only_us.small" in res["metrics"]
