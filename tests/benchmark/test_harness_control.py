"""What decides ``correct`` fails when it should: the control (the
plain reference in the program's place, one precision down or with the
guarantee broken) and each fault a cell can have, planted in the
program under a run that skips only the look for a chip."""

from __future__ import annotations

import json

import pytest

from bench_helpers import cpu_chips, run_cell


def _run_control(root, workload):
    import run
    import spec

    cell = spec.load_cell(workload, root)
    return run.run_once(cell, cpu_chips(cell.chips), 777, 1.0, False,
                        control=True, emit=lambda _l: None)


def _parts(lines):
    return next(json.loads(x) for x in lines if '"wrong_answer_parts"' in x)[
        "wrong_answer_parts"]


@pytest.mark.parametrize("workload", [
    "echo_1chip.bulk64m", "echo_1chip.small4k", "ycsb_1kb.b",
])
def test_control_is_not_correct(tiny, workload):
    res = _run_control(tiny, workload)
    assert res["correct"] is False
    # every sampled answer of the control is wrong
    sampled = next(v["value"] for k, v in res["checks"].items()
                   if k.startswith("sampled_"))
    assert res["checks"]["wrong_answers"]["value"] >= sampled // 2 > 0


def test_echo_answer_altered_at_the_server(tiny, monkeypatch):
    from incubator_brpc_tpu.models.echo import EchoService
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest, EchoResponse
    from incubator_brpc_tpu.server.service import rpc_method

    @rpc_method(EchoRequest, EchoResponse)
    def altered(self, controller, request, response, done):
        response.message = request.message
        for a in controller.request_attachment.device_arrays():
            controller.response_attachment.append_device(a + 1)
        done()

    monkeypatch.setattr(EchoService, "Echo", altered)
    _, lines, res = run_cell(tiny, "echo_1chip.small4k")
    assert res["correct"] is False
    assert _parts(lines)["not_bit_equal"] > 0


def test_cache_update_acknowledged_and_not_applied(tiny, monkeypatch):
    """The store's state left unchanged by an update (the load's first
    SET of each key still lands).  Half the mix updates here, so a few
    dozen requests on a loaded machine hold reads after stale updates."""
    import os

    from bench_helpers import _patch_json
    from incubator_brpc_tpu.cache.store import HBMCacheStore

    _patch_json(os.path.join(tiny, "benchmark", "traffic", "b.json"),
                {"readproportion": 0.5, "updateproportion": 0.5})

    real = HBMCacheStore.set

    def stale(self, key, value):
        if bytes(key) in self:
            return True
        return real(self, key, value)

    monkeypatch.setattr(HBMCacheStore, "set", stale)
    _, lines, res = run_cell(tiny, "ycsb_1kb.b", seconds=1.0)
    assert res["correct"] is False
    assert _parts(lines)["not_linearizable"] > 0


def test_cache_answer_altered_where_produced(tiny, monkeypatch):
    from incubator_brpc_tpu.cache.store import HBMCacheStore

    real = HBMCacheStore.get

    def altered(self, key):
        v = real(self, key)
        return None if v is None else v.at[20].set(v[20] ^ 1)

    monkeypatch.setattr(HBMCacheStore, "get", altered)
    _, lines, res = run_cell(tiny, "ycsb_1kb.b")
    assert res["correct"] is False
    assert _parts(lines)["not_linearizable"] > 0


def test_echo_answer_that_never_comes(tiny, monkeypatch):
    """Requests that fail count as wrong answers: every request after
    the warm-up fails, so the window has failures however few requests
    a loaded machine completes in it."""
    import itertools

    import run
    import spec
    from incubator_brpc_tpu.models.echo import EchoService
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest, EchoResponse
    from incubator_brpc_tpu.server.service import rpc_method

    cell = spec.load_cell("echo_1chip.small4k", tiny)
    warm_requests = run.WARM_CALLS * cell.traffic["callers_per_server"]
    real = EchoService.Echo
    seen = itertools.count()

    @rpc_method(EchoRequest, EchoResponse)
    def never(self, controller, request, response, done):
        if next(seen) >= warm_requests:
            controller.set_failed(1004, "injected")
            done()
            return
        real(self, controller, request, response, done)

    monkeypatch.setattr(EchoService, "Echo", never)
    _, lines, res = run_cell(tiny, "echo_1chip.small4k")
    assert res["correct"] is False and res["failed"] > 0
    assert res["failed"] == res["attempted"]
    assert _parts(lines)["failed"] == res["failed"]
