"""Adaptive micro-batching subsystem tests (batching/, docs/batching.md).

Covers: policy validation, the single-request fallback, coalescing over
real TCP, metrics counting REQUESTS not batches, the deadline guard
(queued-expiry shed before user code + mixed-batch survivors), bounded
jit retraces via padding buckets, the batch.flush chaos site
(deterministic replay + RecoveryHarness clean-shed proof), and the
/batching builtin page."""

import threading
import time

import pytest

from incubator_brpc_tpu import errors
from incubator_brpc_tpu.batching.batcher import Batcher
from incubator_brpc_tpu.batching.policy import BatchPolicy
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.models.parameter_server import PsService, ps_stub
from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest
from incubator_brpc_tpu.server.server import Server, ServerOptions


def make_channel(port, **opts):
    ch = Channel(ChannelOptions(timeout_ms=5000, **opts))
    assert ch.init(f"127.0.0.1:{port}") == 0
    return ch


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------


def test_policy_buckets_and_validation():
    p = BatchPolicy(max_batch_size=8, padding_buckets=(1, 2, 4, 8))
    assert p.enabled
    assert p.bucket_for(1) == 1
    assert p.bucket_for(3) == 4
    assert p.bucket_for(8) == 8
    assert BatchPolicy(max_batch_size=1).enabled is False
    assert BatchPolicy(max_batch_size=0).enabled is False
    # no buckets: no padding (bucket_for is identity)
    assert BatchPolicy(max_batch_size=4).bucket_for(3) == 3
    with pytest.raises(ValueError):
        BatchPolicy(padding_buckets=(4, 2))  # not ascending
    with pytest.raises(ValueError):
        BatchPolicy(padding_buckets=(0, 2))  # non-positive
    with pytest.raises(ValueError):
        # last bucket below max_batch_size would let oversize batches
        # bypass the retrace bound
        BatchPolicy(max_batch_size=32, padding_buckets=(1, 2, 4))
    with pytest.raises(ValueError):
        BatchPolicy(max_wait_us=-1)
    with pytest.raises(ValueError):
        BatchPolicy.from_dict({"max_batch_sized": 3})
    rt = BatchPolicy.from_dict(p.to_dict())
    assert rt.to_dict() == p.to_dict()


def test_off_policy_builds_no_batcher():
    srv = Server(ServerOptions(enable_batching=True,
                               batch_policies={"PsService.Get": None}))
    srv.add_service(PsService())
    assert srv.start(0) == 0
    try:
        # Get force-disabled via overrides; Put rides the decorator default
        assert srv.batcher("PsService.Get") is None
        assert srv.batcher("PsService.Put") is not None
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# dispatch paths over real TCP
# ---------------------------------------------------------------------------


def test_single_request_fallback_without_batching():
    """Batching off (the default): no Batcher exists and the
    synthesized single-request adapter serves the method unchanged."""
    srv = Server()
    srv.add_service(PsService())
    assert srv.start(0) == 0
    try:
        assert not srv._batchers
        stub = ps_stub(make_channel(srv.port))
        c = Controller()
        c.request_attachment.append(b"payload")
        stub.Put(c, EchoRequest(message="k"))
        assert not c.failed(), c.error_text()
        c2 = Controller()
        stub.Get(c2, EchoRequest(message="k"))
        assert not c2.failed(), c2.error_text()
        assert c2.response_attachment.to_bytes() == b"payload"
        c3 = Controller()
        stub.Get(c3, EchoRequest(message="missing"))
        assert c3.failed() and c3.error_code == errors.EREQUEST
    finally:
        srv.stop()


def test_batched_execution_counts_requests_not_batches():
    """Concurrent Gets coalesce into fused executions; the method's
    LatencyRecorder/qps must count ROWS (one per request), the batch
    shape lands in rpc_batch_size/rpc_batch_occupancy, and per-row
    failures don't poison batch-mates."""
    srv = Server(ServerOptions(
        enable_batching=True,
        batch_policies={
            # generous wait so a thread barrier reliably coalesces
            "PsService.Get": BatchPolicy(
                max_batch_size=8, max_wait_us=100_000,
                padding_buckets=(1, 2, 4, 8),
            ),
        },
    ))
    svc = PsService()
    srv.add_service(svc)
    assert srv.start(0) == 0
    svc._store["k"] = b"v"
    nthreads, per_thread = 8, 2
    total = nthreads * per_thread
    results = []
    lock = threading.Lock()
    barrier = threading.Barrier(nthreads, timeout=20)
    try:
        def worker(i):
            ch = make_channel(srv.port)
            stub = ps_stub(ch)
            barrier.wait()
            mine = []
            for j in range(per_thread):
                c = Controller()
                # odd threads interleave a missing key: per-row ERPC
                key = "k" if (i + j) % 2 == 0 else "nope"
                stub.Get(c, EchoRequest(message=key))
                mine.append((key, c.error_code))
            ch.close()
            with lock:
                results.extend(mine)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(results) == total
        for key, code in results:
            if key == "k":
                assert code == 0, f"hit failed with {code}"
            else:
                assert code == errors.EREQUEST, f"miss returned {code}"
        batcher = srv.batcher("PsService.Get")
        assert batcher.rows == total
        assert batcher.batches < total, "nothing coalesced"
        assert batcher.max_batch_seen >= 2, "batcher silently disabled"
        # metrics count requests, not batches
        status = srv.method_status("PsService.Get")
        hits = sum(1 for k, c in results if c == 0)
        assert status.latency_rec.count() == hits
        assert status.errors.get_value() == total - hits
        # exposed per-method batch variables (on /vars and /metrics)
        from incubator_brpc_tpu.metrics.variable import _registry

        size_var = _registry.get("rpc_batch_size_psservice_get")
        occ_var = _registry.get("rpc_batch_occupancy_psservice_get")
        assert size_var is not None and occ_var is not None
        s, n = size_var.sum_num()
        assert n == batcher.batches and s == batcher.rows
        assert 0.0 < occ_var.get_value() <= 1.0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# deadline guard
# ---------------------------------------------------------------------------


class _RecordingHandler:
    def __init__(self):
        self.batches = []

    def __call__(self, controllers, requests, responses, done):
        self.batches.append(list(controllers))
        done()


def _row(deadline_ns=0):
    ctrl = Controller()
    if deadline_ns:
        ctrl._batch_deadline_ns = deadline_ns
    from incubator_brpc_tpu.observability.span import Span

    ctrl._span = Span("server", "T", "M")
    calls = []
    return ctrl, calls, (lambda: calls.append(1))


def test_mixed_batch_sheds_expired_row_and_executes_survivors():
    """A flush window holding one expired and one live row sheds the
    expired row BEFORE user code (exactly one ELIMIT completion, shed
    phase stamped on its span) and still executes the survivor."""
    from incubator_brpc_tpu.batching.batcher import _Row

    handler = _RecordingHandler()
    b = Batcher(
        "T.M", handler,
        BatchPolicy(max_batch_size=2, max_wait_us=50_000),
        inline=True,
    )
    try:
        now = time.monotonic_ns()
        dead_ctrl, dead_calls, dead_done = _row()
        live_ctrl, live_calls, live_done = _row()
        b._flush([
            _Row(dead_ctrl, "r1", "s1", dead_done, now - 5_000_000,
                 now - 1_000_000),  # expired while queued
            _Row(live_ctrl, "r2", "s2", live_done, now, 0),
        ])
        # the mixed batch executed its surviving row...
        assert handler.batches == [[live_ctrl]]
        assert live_calls == [1] and not live_ctrl.failed()
        # ...and the expired row was shed BEFORE user code, exactly one
        # completion, ELIMIT, with the shed phase stamped on its span
        assert dead_calls == [1]
        assert dead_ctrl.error_code == errors.ELIMIT
        assert "batch_shed" in dead_ctrl._span.describe()
        assert b.shed.get_value() == 1
        assert b.rows == 1 and b.batches == 1
    finally:
        b.stop()


def test_row_already_past_deadline_at_submit_never_reaches_user_code():
    """The guard clamps the flush-by time to (deadline - service EMA):
    a row arriving with its budget already gone flushes immediately and
    sheds without the handler ever running."""
    handler = _RecordingHandler()
    b = Batcher(
        "T.M", handler,
        BatchPolicy(max_batch_size=8, max_wait_us=1_000_000),
        inline=True,
    )
    try:
        dead_ctrl, dead_calls, dead_done = _row(
            deadline_ns=time.monotonic_ns() - 1_000_000
        )
        assert b.submit(dead_ctrl, "r1", "s1", dead_done)
        assert dead_calls == [1]
        assert dead_ctrl.error_code == errors.ELIMIT
        assert handler.batches == [], "user code ran for an expired row"
        assert b.pending() == 0
    finally:
        b.stop()


def test_deadline_guard_flushes_before_budget_exhausted():
    """A queued row's flush must come no later than
    (deadline - expected service time), far ahead of max_wait_us."""
    handler = _RecordingHandler()
    done_ev = threading.Event()
    b = Batcher(
        "T.M", handler,
        BatchPolicy(
            max_batch_size=8,
            max_wait_us=2_000_000,  # 2s: would blow the deadline
            deadline_us=100_000,  # 100ms budget
            expected_service_us=20_000,  # guard => flush by ~80ms
        ),
    )
    try:
        ctrl = Controller()
        t0 = time.monotonic()
        assert b.submit(ctrl, "r", "s", done_ev.set)
        assert done_ev.wait(1.5), "flush never fired"
        elapsed = time.monotonic() - t0
        assert elapsed < 0.5, f"flush waited {elapsed:.2f}s (deadline guard dead)"
        assert handler.batches and handler.batches[0][0] is ctrl
        assert not ctrl.failed(), "row shed instead of executed"
    finally:
        b.stop()


def test_deadline_shed_over_tcp_closes_span():
    """End to end: a request whose deadline expires while queued comes
    back ELIMIT and its server span closes carrying the shed stamp."""
    from incubator_brpc_tpu.chaos.harness import wait_until
    from incubator_brpc_tpu.observability.span import span_db
    from incubator_brpc_tpu.utils.flags import get_flag, set_flag

    prev = get_flag("rpcz_enabled", True)
    set_flag("rpcz_enabled", True)
    srv = Server(ServerOptions(
        enable_batching=True,
        batch_policies={
            # 1us budget: always expired by flush time
            "PsService.Get": BatchPolicy(
                max_batch_size=8, max_wait_us=30_000, deadline_us=1,
            ),
        },
    ))
    svc = PsService()
    srv.add_service(svc)
    assert srv.start(0) == 0
    svc._store["k"] = b"v"
    try:
        stub = ps_stub(make_channel(srv.port))
        c = Controller()
        stub.Get(c, EchoRequest(message="k"))
        assert c.failed() and c.error_code == errors.ELIMIT, c.error_text()
        assert srv.batcher("PsService.Get").shed.get_value() >= 1
        # the span closes through the normal error-response path with
        # the shed phase stamped (Collector drains in rounds: wait)
        assert wait_until(
            lambda: any(
                s.kind == "server" and "batch_shed" in s.describe()
                for s in span_db().recent(200)
            ),
            timeout_s=3.0,
        ), "no server span with the shed stamp reached the SpanDB"
    finally:
        srv.stop()
        set_flag("rpcz_enabled", prev)


def test_queue_cap_sheds_overflow_instead_of_growing_unbounded():
    """Batches execute one at a time per method, so sustained overload
    accumulates in the queue: a row arriving at max_queue_rows is shed
    EOVERCROWDED at admission, exactly one completion, and the queue
    never exceeds the cap."""
    release = threading.Event()

    def blocking_handler(controllers, requests, responses, done):
        release.wait(10)
        done()

    b = Batcher(
        "T.M", blocking_handler,
        BatchPolicy(max_batch_size=2, max_wait_us=1_000_000,
                    max_queue_rows=4),
    )
    try:
        rows = [_row() for _ in range(8)]
        for ctrl, _, done in rows:
            assert b.submit(ctrl, "r", "s", done)
        time.sleep(0.3)  # first window (2 rows) is now in flight, blocked
        assert b.pending() == 4, b.pending()  # 2 in flight + 4 queued = cap
        shed = [r for r in rows if r[0].failed()]
        assert len(shed) == 2  # rows 7 and 8 arrived at a full queue
        for ctrl, calls, _ in shed:
            assert ctrl.error_code == errors.EOVERCROWDED
            assert calls == [1], "shed row completed more than once"
            assert "batch_shed" in ctrl._span.describe()
        assert b.shed.get_value() == 2
        release.set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if all(calls == [1] for _, calls, _ in rows):
                break
            time.sleep(0.01)
        # the 6 admitted rows all executed once the handler unblocked
        assert all(calls == [1] for _, calls, _ in rows)
        assert not any(r[0].failed() for r in rows if r not in shed)
    finally:
        b.stop()


# ---------------------------------------------------------------------------
# padding buckets bound jit retraces
# ---------------------------------------------------------------------------


def test_padding_buckets_bound_jit_retraces():
    import jax.numpy as jnp

    from incubator_brpc_tpu.batching import fused
    from incubator_brpc_tpu.batching.fused import StagingRing

    policy = BatchPolicy(max_batch_size=8, padding_buckets=(1, 2, 4, 8))
    ring = StagingRing(depth=8, max_keys=4)
    row = jnp.arange(16, dtype=jnp.float32)
    before = fused.trace_count()
    for n in range(1, 9):
        outs = fused.fused_stack_rows(
            [row] * n, policy.bucket_for(n), freelist=ring
        )
        assert len(outs) == n
        for o in outs:
            assert o.shape == row.shape
            assert jnp.array_equal(o, row)
    retraces = fused.trace_count() - before
    assert retraces <= len(policy.padding_buckets), (
        f"{retraces} retraces for 8 batch sizes; buckets must bound it "
        f"at {len(policy.padding_buckets)}"
    )
    # the padding freelist is bounded: slots recycle, never accumulate
    # beyond the ring's depth for the single row key
    total_slots = sum(len(q) for q in ring._slots.values())
    assert total_slots <= ring.depth


# ---------------------------------------------------------------------------
# chaos: batch.flush
# ---------------------------------------------------------------------------


def _flush_n_times(batcher, n):
    """Drive n deterministic inline flushes (2 rows each)."""
    for _ in range(n):
        c1, _, d1 = _row()
        c2, _, d2 = _row()
        batcher.submit(c1, "a", "x", d1)
        batcher.submit(c2, "b", "y", d2)


def test_chaos_batch_flush_replay_fires_identical_traversals():
    from incubator_brpc_tpu.chaos import FaultPlan, FaultSpec
    from incubator_brpc_tpu.chaos import injector

    plan = FaultPlan(
        [FaultSpec(site="batch.flush", action="delay_us", arg=1,
                   every_nth=3)],
        seed=42, name="flush-replay",
    )
    handler = _RecordingHandler()

    def one_run():
        # generous wait: a >1ms stall between the two submits must not
        # split a window (a timer flush would add a traversal index)
        b = Batcher("T.M", handler,
                    BatchPolicy(max_batch_size=2, max_wait_us=100_000),
                    inline=True)
        injector.arm(plan)
        try:
            _flush_n_times(b, 9)
            return injector.hit_log()
        finally:
            injector.disarm()
            b.stop()

    log1 = one_run()
    log2 = one_run()
    assert log1 == log2, "replay diverged"
    assert [n for (_, _, n) in log1] == [2, 5, 8]
    assert all(site == "batch.flush" for (site, _, _) in log1)


def test_chaos_flush_drop_sheds_cleanly_under_recovery_harness():
    """A dropped flush decision sheds its whole window: every batched
    controller completes exactly once with an ERPC code, the batcher
    queue drains, and no freelist slot leaks."""
    from incubator_brpc_tpu.chaos import FaultPlan, FaultSpec, RecoveryHarness

    srv = Server(ServerOptions(
        enable_batching=True,
        batch_policies={
            "PsService.Get": BatchPolicy(
                max_batch_size=4, max_wait_us=20_000,
                padding_buckets=(1, 2, 4),
            ),
        },
    ))
    svc = PsService()
    srv.add_service(svc)
    assert srv.start(0) == 0
    svc._store["k"] = b"v"
    batcher = srv.batcher("PsService.Get")
    plan = FaultPlan(
        [FaultSpec(site="batch.flush", action="drop", every_nth=2,
                   max_hits=2, match={"method": "PsService.Get"})],
        seed=7, name="flush-drop",
    )

    def freelist_slots():
        return sum(len(q) for q in batcher.pad_freelist._slots.values())

    harness = RecoveryHarness(
        plan,
        wall_clock_s=20.0,
        baseline_probes=[
            ("batch_queue_depth", batcher.pending),
            ("pad_freelist_slots", freelist_slots),
        ],
    )
    total = [0]

    def workload(h):
        lock = threading.Lock()

        def worker():
            ch = make_channel(srv.port)
            stub = ps_stub(ch)
            for _ in range(4):
                c = Controller()
                stub.Get(c, EchoRequest(message="k"))
                h.record_error(c.error_code)
                with lock:
                    total[0] += 1
            ch.close()

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    try:
        report = harness.run_or_raise(workload)
        # every call completed exactly once (none hung on a lost flush)
        assert len(report.error_codes) == total[0] == 16
        dropped = [c for c in report.error_codes if c != 0]
        hits = report.hits.get("batch.flush", {}).get("drop", 0)
        assert hits >= 1, "the drop never fired"
        assert dropped, "a dropped flush produced no shed completions"
        assert all(c == errors.EOVERCROWDED for c in dropped), dropped
        assert batcher.shed.get_value() == len(dropped)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# /batching builtin + runtime tuning
# ---------------------------------------------------------------------------


def test_batching_page_get_post_and_status_surfacing():
    import json
    import socket as _pysocket

    from incubator_brpc_tpu.tools.rpc_view import fetch_page

    srv = Server(ServerOptions(enable_batching=True,
                               method_max_concurrency="auto"))
    srv.add_service(PsService())
    assert srv.start(0) == 0
    try:
        state = json.loads(fetch_page(f"127.0.0.1:{srv.port}", "batching"))
        assert state["enabled"] is True
        get_state = state["methods"]["PsService.Get"]
        assert get_state["policy"]["max_batch_size"] == 32
        assert {"pending", "occupancy", "batches", "rows", "shed"} <= set(get_state)
        # POST tunes max_wait_us at runtime
        with _pysocket.create_connection(("127.0.0.1", srv.port), timeout=3) as s:
            s.sendall(
                b"POST /batching?method=PsService.Get&max_wait_us=123 "
                b"HTTP/1.1\r\nHost: x\r\nContent-Length: 0\r\n"
                b"Connection: close\r\n\r\n"
            )
            data = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                data += chunk
        assert b"200" in data.split(b"\r\n", 1)[0]
        assert srv.batcher("PsService.Get").policy.max_wait_us == 123
        # unknown method → 404
        with _pysocket.create_connection(("127.0.0.1", srv.port), timeout=3) as s:
            s.sendall(
                b"POST /batching?method=No.Such&max_wait_us=5 HTTP/1.1\r\n"
                b"Host: x\r\nContent-Length: 0\r\nConnection: close\r\n\r\n"
            )
            data = s.recv(65536)
        assert b"404" in data.split(b"\r\n", 1)[0]
        # /status surfaces the limiter's moving max_concurrency AND the
        # batcher's live queue depth per method
        status = fetch_page(f"127.0.0.1:{srv.port}", "status")
        assert "limiter=AutoConcurrencyLimiter max_concurrency=" in status
        assert "batching: queue_depth=" in status
    finally:
        srv.stop()


def test_disable_method_batching_restores_direct_path():
    srv = Server(ServerOptions(enable_batching=True))
    svc = PsService()
    srv.add_service(svc)
    assert srv.start(0) == 0
    svc._store["k"] = b"v"
    try:
        assert srv.batcher("PsService.Get") is not None
        srv.disable_method_batching("PsService.Get")
        assert srv.batcher("PsService.Get") is None
        stub = ps_stub(make_channel(srv.port))
        c = Controller()
        stub.Get(c, EchoRequest(message="k"))
        assert not c.failed(), c.error_text()
        assert c.response_attachment.to_bytes() == b"v"
    finally:
        srv.stop()


def test_staging_ring_bookkeeping():
    import numpy as np

    from incubator_brpc_tpu.batching.fused import StagingRing

    ring = StagingRing(depth=2, max_keys=2)
    assert ring.acquire((4, 4), "float32") is None  # cold: caller allocates
    a = np.zeros((4, 4), dtype=np.float32)
    ring.release(a)
    got = ring.acquire((4, 4), "float32")
    assert got is a
    assert ring.acquire((4, 4), "float32") is None  # ring emptied
    # depth bound: a third same-shape release is dropped
    b, c, d = (np.zeros((4, 4), dtype=np.float32) for _ in range(3))
    for arr in (b, c, d):
        ring.release(arr)
    assert ring.acquire((4, 4), "float32") is b
    assert ring.acquire((4, 4), "float32") is c
    assert ring.acquire((4, 4), "float32") is None
    # key bound: LRU shape evicted when a third shape arrives
    ring.release(np.zeros((4, 4), dtype=np.float32))    # key A (recent)
    ring.release(np.zeros((8, 8), dtype=np.float32))    # key B
    ring.acquire((4, 4), "float32")                     # touch A → B is LRU
    ring.release(np.zeros((2, 2), dtype=np.float32))    # key C evicts B
    assert ring.acquire((8, 8), "float32") is None
    assert ring.acquire((2, 2), "float32") is not None
