"""``ycsb_full.a`` at a tiny size on the CPU: the bulk value maker is
bit-equal to the reference's, the cell runs correct and its control
does not, a program without the fused DMSET stops the run before its
load, and the two new readers (``store_us``, ``slab_roofline_pct``)
read constructed captures and traces."""

from __future__ import annotations

import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench_helpers import _patch_json, cpu_chips, make_copy, run_cell

CELL = "ycsb_full.a"


@pytest.fixture
def tiny_full(tmp_path):
    root = make_copy(str(tmp_path))
    _patch_json(os.path.join(root, "benchmark", "configs", "ycsb_full.json"),
                {"recordcount": 9000,
                 "store_options": {"hbm_budget_bytes": 64 << 20}})
    _patch_json(os.path.join(root, "benchmark", "traffic", "a.json"),
                {"callers_per_server": 2, "sample": 512})
    return root


def _system(root):
    import spec

    cell = spec.load_cell(CELL, root)
    return cell, spec.system_module(cell)


def test_bulk_values_equal_values_make(tiny_full):
    _, sysmod = _system(tiny_full)
    values = sysmod.base.Values(2**31 + 12345, 1000)
    bulk = sysmod.BulkValues(values)
    keys = np.array([0, 1, 2, 4095, 4096, 777_777, 7_999_999])
    dev = cpu_chips(1)[0]
    rows = np.asarray(bulk.rows(keys, dev))
    assert rows.shape == (len(keys), 1000)
    for k, row in zip(keys.tolist(), rows):
        assert row.tobytes() == values.make(k, 0)
    ver = (3 << 40 >> 2) | 17  # a caller's version, under 2**40
    rows = np.asarray(bulk.rows(keys, dev, version=ver))
    for k, row in zip(keys.tolist(), rows):
        assert row.tobytes() == values.make(k, ver)


def test_cell_runs_correct_and_reads_its_metrics(tiny_full):
    rc, lines, res = run_cell(tiny_full, CELL, seconds=1.0, trace=1)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["checks"]["sampled_reads"]["value"] >= 1
    m = res["metrics"]
    for name in ("host_only_us.full", "cq_wait_us.full", "service_us.full",
                 "store_us.full", "client_us.full", "fabric_us.full"):
        assert m[name]["value"] > 0, m
    assert m["store_us.full"]["value"] <= m["service_us.full"]["value"]
    import json

    counters = next(json.loads(x) for x in lines if '"counters"' in x)["counters"]
    # half the window's requests are updates, each one row written in place
    assert counters["rpc_cache_slab_writes"] == counters["rpc_cache_slab_write_programs"] > 0
    # a GET reply leaves the store handed off: no transmit copy, checked or not
    assert counters["rpc_ici_unchecked_segments"] == 0


def test_control_is_not_correct(tiny_full):
    import run

    cell, _ = _system(tiny_full)
    res = run.run_once(cell, cpu_chips(1), 777, 1.0, False, control=True,
                       emit=lambda _l: None)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_a_program_without_fused_dmset_stops_before_the_load(tiny_full,
                                                            monkeypatch):
    from incubator_brpc_tpu.cache.service import HBMCacheService
    from incubator_brpc_tpu.protocols.redis import RedisReply

    stores = []
    real = HBMCacheService.dmset

    def per_key_only(self, *args):
        stores.append(self.store)
        if len(args) % 2:
            return RedisReply.error("ERR wrong number of arguments for 'dmset'")
        return real(self, *args)

    monkeypatch.setattr(HBMCacheService, "dmset", per_key_only)
    cell, sysmod = _system(tiny_full)
    with pytest.raises(sysmod.NoFusedDmset):
        sysmod.build(cell, cpu_chips(1), 99)
    assert len(stores) == 1 and len(stores[0]) == 0
    # the failed build stopped its server: the chip's coords are free
    from incubator_brpc_tpu.parallel.ici import get_fabric

    assert get_fabric().port((0, cell.config["chip"])) is None


def _cap(*servers, start=1000, stop=9000):
    spans = []
    for i, stamps in enumerate(servers):
        t = start + 100 * (i + 1)
        spans += [
            SimpleNamespace(kind="client", trace_id=i, span_id=1, parent_span_id=0,
                            service="redis", error_code=0, start_us=t,
                            response_write_us=t + 5, received_us=t + 40,
                            dequeued_us=t + 41, end_us=t + 50),
            SimpleNamespace(kind="server", trace_id=i, span_id=2, parent_span_id=1,
                            service="redis", error_code=0, received_us=t + 10,
                            dequeued_us=t + 12, callback_start_us=t + 15,
                            callback_done_us=t + 30, **stamps),
        ] + [SimpleNamespace(kind="collective", trace_id=i, span_id=3 + j,
                             parent_span_id=1, service="ici", error_code=0,
                             start_us=t + 6 + 30 * j, placed_us=t + 8 + 30 * j)
             for j in range(2)]
    return SimpleNamespace(spans=spans, start_us=start, stop_us=stop, overflow=0)


def test_store_us_reads_the_stamps_and_none_without(tiny_full, monkeypatch):
    import rpcz_capture as RC
    import spec

    cell = spec.load_cell(CELL, tiny_full)
    read = spec.metric_reader(cell, "store_us.full")
    traced = SimpleNamespace(trace=object())
    monkeypatch.setattr(RC, "last_capture", lambda: _cap(
        {"store_start_us": 1117, "store_done_us": 1127},
        {"store_start_us": 1218, "store_done_us": 1224}))
    assert read(traced) == pytest.approx(8.0)
    assert read(SimpleNamespace(trace=None)) is None
    # a program whose server spans carry no store stamps
    monkeypatch.setattr(RC, "last_capture", lambda: _cap({}, {}))
    assert read(traced) is None


def test_slab_roofline_counts_programs_inside_requests(tiny_full):
    import spec
    import slab_kernels
    from tracereduce import Event, Trace

    tr = Trace(
        modules={0: [Event(10, 20, "jit_cache_slab_read(1)"),
                     Event(50, 54, "jit_cache_slab_write(2)"),
                     Event(38, 46, "jit_cache_slab_write(2)"),  # straddles
                     Event(60, 70, "jit_other(3)")]},
        spans={"bench.window": [(0, 100)],
               "bench.request": [(5, 40), (45, 80), (90, 110)]})
    assert slab_kernels.programs_in_requests(tr, [0]) == (2, pytest.approx(14e-9))
    cell = spec.load_cell(CELL, tiny_full)
    run = SimpleNamespace(trace=tr, chips=[0], bench=SimpleNamespace(frame_bytes=1000),
                          peaks={"hbm_bytes_per_s": 1e12})
    got = spec.metric_reader(cell, "slab_roofline_pct")(run)
    assert got == pytest.approx(100.0 * 2 * 2000 / 1e12 / 14e-9)
    assert spec.metric_reader(cell, "slab_roofline_pct")(
        SimpleNamespace(trace=Trace(spans=tr.spans), chips=[0])) is None
