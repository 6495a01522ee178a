"""Bench smoke: short versions of the 4KB-echo and size-curve bench
sections run in tier-1 CI so a hot-path regression (like the round-5
64KB crater: 8x qps loss at one payload point, healing at 256KB) can't
land silently.  Thresholds are deliberately loose — this one-core host
swings ±30% run to run — but an order-of-magnitude crater or a broken
fast path fails loudly.
"""

import pytest

from incubator_brpc_tpu import native
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import (
    acquire_controller,
    release_controller,
)
from incubator_brpc_tpu.models.echo import EchoService, echo_stub
from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest
from incubator_brpc_tpu.server.server import Server, ServerOptions
from incubator_brpc_tpu.server.service import RAW_RESPONSE

# applied per-test (not module-wide): the streaming-generate guard at
# the bottom runs on the pure-Python transport and needs no engine
needs_native = pytest.mark.skipif(
    not native.available(), reason="native engine not built"
)


@pytest.fixture(scope="module")
def echo_server():
    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(EchoService(attach_echo=False))
    assert srv.start(0) == 0
    yield srv
    srv.stop()


def _best_gbps(port, psize, cfgs, duration_ms=500):
    best = 0.0
    for conc, depth, conns in cfgs:
        r = native.bench_echo(
            "127.0.0.1", port, psize, concurrency=conc,
            duration_ms=duration_ms, depth=depth, conns=conns,
        )
        if r["failed"] == 0:
            best = max(best, r["qps"] * psize / 1e9)
    return best


@needs_native
def test_echo_4kb_native_smoke(echo_server):
    """The native 4KB echo must stay within an order of magnitude of
    its measured level (~150-400k qps pipelined on this host)."""
    r = native.bench_echo(
        "127.0.0.1", echo_server.port, 4096, concurrency=1,
        duration_ms=700, depth=32, conns=1,
    )
    assert r["failed"] == 0
    assert r["qps"] > 40_000, r


@needs_native
def test_echo_size_curve_no_crater(echo_server):
    """The 64KB point must not crater relative to its neighbours.
    Round 5 shipped 64KB at ~1/8th of 16KB (staging double-copy +
    malloc mmap churn); the guard allows generous noise but not that."""
    cfgs = [(2, 1, 1), (1, 16, 1)]
    g16 = _best_gbps(echo_server.port, 16384, cfgs)
    g64 = _best_gbps(echo_server.port, 65536, cfgs)
    g256 = _best_gbps(echo_server.port, 262144, cfgs)
    assert g16 > 0 and g64 > 0 and g256 > 0
    assert g64 >= 0.45 * g16, f"64KB crater: {g64:.2f} vs 16KB {g16:.2f}"
    assert g64 >= 0.35 * g256, f"64KB crater: {g64:.2f} vs 256KB {g256:.2f}"


@needs_native
def test_chaos_disarmed_overhead_guard(echo_server):
    """The fault-injection sites must be invisible on the disarmed echo
    hot path (<1% budget, bench.py chaos_disarmed_overhead measures it
    precisely with long drift-cancelling segments).  This quick guard
    runs the SAME estimator (bench._drift_cancelled_overhead) on short
    segments; the bound is set above this host's run-to-run noise so it
    cannot flake, while an accidentally expensive disarmed path — a
    site taking a lock, iterating specs, or re-importing per call —
    still fails loudly (such bugs cost tens of percent, not single
    digits)."""
    import statistics
    import time

    from bench import _drift_cancelled_overhead
    from incubator_brpc_tpu.chaos import FaultPlan
    from incubator_brpc_tpu.chaos import injector as chaos_injector
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    ch = Channel(ChannelOptions(timeout_ms=10000))  # python transport:
    ch.init(f"127.0.0.1:{echo_server.port}")  # traverses every py site
    stub = echo_stub(ch)
    req = EchoRequest(message="x" * 4096)
    empty_plan = FaultPlan([], seed=1, name="empty")

    def seg(calls=150):
        t0 = time.monotonic()
        for _ in range(calls):
            c = Controller()
            stub.Echo(c, req)
            assert not c.error_code, c.error_text()
        return calls / (time.monotonic() - t0)

    try:
        _, _, deltas = _drift_cancelled_overhead(
            seg,
            lambda: chaos_injector.arm(empty_plan),
            chaos_injector.disarm,
            pairs=4,
        )
        overhead = statistics.median(deltas)
        assert overhead < 8.0, (
            f"disarmed chaos sites cost {overhead:.1f}% on the echo hot "
            f"path (budget <1%; this guard allows noise up to 8%) — "
            f"deltas {deltas}"
        )
    finally:
        chaos_injector.disarm()
        ch.close()


@needs_native
def test_echo_4kb_pyapi_smoke(echo_server):
    """A burst of pooled Python-API calls rides the native mux, not the
    Python transport (full path: stub → fused call_method →
    mux_call_fast): the mux's own counters account for every call."""
    import threading

    ch = Channel(ChannelOptions(timeout_ms=5000, connection_type="native"))
    ch.init(f"127.0.0.1:{echo_server.port}")
    stub = echo_stub(ch)
    packed = EchoRequest(message="x" * 4096).SerializeToString()
    try:
        total, nthreads = 6000, 8
        ok = []
        lock = threading.Lock()

        def worker():
            n = 0
            call = stub.Echo
            for _ in range(total // nthreads):
                c = acquire_controller()
                call(c, packed, response=RAW_RESPONSE)
                if not c.error_code:
                    n += 1
                release_controller(c)
            with lock:
                ok.append(n)

        mux = ch._native_mux()
        assert mux is not None, "native mux did not start"
        before = mux.stats()
        ts = [threading.Thread(target=worker) for _ in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        after = mux.stats()
        assert sum(ok) == total
        # counted events, not a wall-clock rate: a call that fell back
        # to the Python transport never reaches these C-side counters
        assert after["ok"] - before["ok"] == total, (before, after)
        assert after["fail"] - before["fail"] == 0, (before, after)
    finally:
        ch.close()


@needs_native
def test_ring_bench_structure_guard(echo_server):
    """Structure guard for the pyapi_ring_curve bench lane (NOT
    absolute qps — the ≥2x-sync / within-~2x-native acceptance comes
    from the full bench on a quiet host): a short batched drive on the
    native lane must prove the ring is actually vectorized by step
    log — boundary_crossings ≪ calls (a silently-degraded ring crosses
    per call and reads ≈ 2*calls), harvest_batches ≥ 2, ZERO fallback
    calls, zero double resolves — and the C-side mux counters must
    agree that whole windows crossed."""
    ch = Channel(ChannelOptions(timeout_ms=5000, connection_type="native"))
    ch.init(f"127.0.0.1:{echo_server.port}")
    stub = echo_stub(ch)
    packed = EchoRequest(message="x" * 4096).SerializeToString()
    window, nwin = 32, 40
    calls = window * nwin
    try:
        spec = stub.method_spec("Echo")
        ring = ch.submission_ring(depth=window)
        reqs = [packed] * window
        ok = 0
        for _ in range(nwin):
            ring.submit_all(spec, reqs)
            for _slot, res in ring.drain():
                if isinstance(res, bytes):
                    ok += 1
        assert ok == calls
        c = ring.counters()
        assert c["submissions"] == calls
        assert c["fallback_calls"] == 0, c
        assert c["double_resolves"] == 0, c
        assert c["harvest_batches"] >= 2, c
        # vectorization floor: ≤ 1 submit + ~1 harvest crossing per
        # window plus slack, nowhere near the 2-per-call degraded shape
        assert c["boundary_crossings"] <= calls / 4, c
        stats = ch._native_mux().ring_stats()
        assert stats["calls"] >= calls
        assert stats["windows"] <= stats["calls"] / 4, stats
    finally:
        ch.close()


@needs_native
def test_ring_window_hits_micro_batcher_smoke():
    """A batched-method call_many window must land in the server
    micro-batcher as ONE accumulation (observed batch ≥ window/2, the
    acceptance floor) — Echo is answered natively in C and never
    reaches the Python batcher, so this drives PsService.Get."""
    from incubator_brpc_tpu.batching.policy import BatchPolicy
    from incubator_brpc_tpu.models.parameter_server import PsService, ps_stub

    srv = Server(ServerOptions(
        native_engine=True,
        enable_batching=True,
        batch_policies={
            "PsService.Get": BatchPolicy(
                max_batch_size=32, max_wait_us=100_000
            ),
        },
    ))
    svc = PsService()
    srv.add_service(svc)
    assert srv.start(0) == 0
    svc._store["k"] = b"v" * 64
    ch = Channel(ChannelOptions(timeout_ms=5000, connection_type="native"))
    assert ch.init(f"127.0.0.1:{srv.port}") == 0
    stub = ps_stub(ch)
    try:
        w = 16
        res = stub.call_many(
            "Get", [EchoRequest(message="k").SerializeToString()] * w
        )
        assert all(isinstance(r, bytes) for r in res), res
        b = srv.batcher("PsService.Get")
        assert b.max_batch_seen >= w // 2, b.describe()
    finally:
        srv.stop()
        ch.close()


@needs_native
def test_shard_window_bench_structure_guard():
    """Structure guard for the bench_shard_window lane (NOT absolute
    qps): a small run must prove the windowed shard fan-out crossed
    the C boundary once per SHARD, not once per key — crossings ≪
    calls, keys_per_crossing = n_keys/shards — with ZERO per-call
    fallbacks on the windowed path, and the cache get_many half must
    cross once per balancer group.  A silently-degraded fan-out (every
    key its own crossing) fails the ≪ bound loudly."""
    from bench import bench_shard_window

    n_keys, shards, reps = 24, 2, 1
    out = bench_shard_window(
        n_keys=n_keys, shards=shards, value_bytes=64, reps=reps
    )
    assert "shard_window_error" not in out, out
    ps = out["shard_window_ps"]
    assert ps["windows"] == reps, ps
    assert ps["windowed_crossings"] == shards * reps, ps
    assert ps["windowed_crossings"] <= n_keys // 4, ps  # crossings ≪ calls
    assert ps["fallback_calls"] == 0, ps
    assert ps["keys_per_crossing"] == n_keys / shards, ps
    cache = out["shard_window_cache"]
    assert cache["fallback_calls"] == 0, cache
    # one DMGET crossing per balancer group per get_many — never per key
    assert 0 < cache["get_many_crossings"] <= cache["replicas"] * reps, cache
    assert 0 < cache["set_many_crossings"] <= cache["replicas"], cache


@needs_native
def test_server_ring_bench_structure_guard(echo_server):
    """Structure guard for the server-ring flavor of pyapi_ring_curve:
    a batched window driven at the native server must advance the
    engine's reply step log with windows ≪ responses (one writev burst
    per harvested window — a per-call reply path reports windows ≈
    responses) and flush_bursts tracking windows."""
    def srv_stats():
        return echo_server._engine_op(lambda eng: dict(eng.ring_stats()))

    ch = Channel(ChannelOptions(timeout_ms=5000, connection_type="native"))
    assert ch.init(f"127.0.0.1:{echo_server.port}") == 0
    stub = echo_stub(ch)
    packed = EchoRequest(message="x" * 1024).SerializeToString()
    window, nwin = 32, 4
    try:
        spec = stub.method_spec("Echo")
        ring = ch.submission_ring(depth=window)
        before = srv_stats()
        ok = 0
        for _ in range(nwin):
            ring.submit_all(spec, [packed] * window)
            for _slot, res in ring.drain():
                if isinstance(res, bytes):
                    ok += 1
        after = srv_stats()
        assert ok == window * nwin
        resp_d = after["responses"] - before["responses"]
        win_d = after["windows"] - before["windows"]
        burst_d = after["flush_bursts"] - before["flush_bursts"]
        assert resp_d >= window * nwin * 3 // 4, (before, after)
        assert 1 <= win_d <= max(2 * nwin, resp_d // 4), (before, after)
        assert burst_d >= win_d, (before, after)
    finally:
        ch.close()


@needs_native
def test_ici_bench_structure_and_dispatch_guard():
    """Structure/regression guard for the ICI bench cases (NOT absolute
    numbers — the real ici_64mb_echo_gbps / ici_rpc_dispatch_p50_us
    levels are bench-host properties): a tiny-payload run must produce
    the headline keys, complete every echo, and keep dispatch p50
    within an order-of-magnitude sanity bound, so a broken fabric path
    (per-call reconnects, a wedged completion queue, a placement fault)
    fails loudly in CI."""
    from bench import bench_ici_rpc

    out = bench_ici_rpc(mb=1, hi=4, lo=2, reps=2)
    assert out.get("ici_rpc_ok", 0) >= 12, out
    assert 0 < out["ici_rpc_dispatch_p50_us"] < 200_000, out
    assert "ici_echo_e2e_us_per_echo_all" in out
    if out.get("ici_echo_e2e_us_per_echo_median", 0) > 0:
        assert out.get("ici_64mb_echo_gbps", 0) > 0, out


@needs_native
def test_batched_device_op_structure_guard():
    """Structure/regression guard for the micro-batching bench case
    (NOT absolute numbers — the ≥3x speedup at parallelism ≥16 is a
    TPU-host property; this one-core CPU host pays the flush handoff
    with nothing to amortize): a tiny run must produce both configs,
    complete calls on each, and show the batcher actually coalescing —
    a silently-disabled batcher reads observed_max_batch == 1 here and
    fails loudly."""
    from bench import bench_batched_device_op

    out = bench_batched_device_op(
        parallelism=(6,), batch_sizes=(6,), duration_s=0.5, dim=16
    )
    d = out["batched_device_op"]
    points = {p["config"]: p for p in d["points"]}
    assert set(points) == {"off", "on6"}, points
    assert points["off"]["ok"] > 0 and points["on6"]["ok"] > 0
    on = points["on6"]
    assert on["observed_batches"] > 0, "batched config never flushed"
    assert on["observed_max_batch"] >= 2, (
        f"6 concurrent callers never coalesced "
        f"(max batch {on['observed_max_batch']}): batcher silently disabled"
    )
    assert "speedup_vs_off" in on and "p99_vs_off_p50" in on
    assert "best_speedup_at_p6" in d


def test_resharding_bulk_move_bench_structure_guard():
    """Structure guard for bench_resharding_bulk_move (NOT wall time —
    the CPU smoke run is compile-dominated; the collective win is
    measured on TPU): both lanes must complete and move every key, the
    bulk lane must move them in ≤3 collective steps per owner-changing
    range (read_many → write_many → verify) with steps ≪ keys, and the
    stripped per-key lane must record ZERO collective steps — so a
    bulk lane that silently degrades to per-key RPCs fails loudly."""
    from bench import bench_resharding_bulk_move

    out = bench_resharding_bulk_move(n_keys=16, value_bytes=512)
    assert "resharding_bulk_move_error" not in out, out
    d = out["resharding_bulk_move"]
    bulk, per_key = d["bulk"], d["per_key"]
    assert bulk["completed"] and per_key["completed"], d
    assert bulk["keys_moved"] == per_key["keys_moved"] > 0, d
    assert bulk["bulk_ranges"] > 0, d
    assert bulk["collective_steps"] <= 3 * bulk["bulk_ranges"], d
    assert bulk["collective_steps"] < bulk["keys_moved"], (
        f"bulk lane took {bulk['collective_steps']} steps for "
        f"{bulk['keys_moved']} keys: not a collective lowering"
    )
    assert per_key["collective_steps"] == 0, (
        "stripped per-key lane recorded collective steps: the bulk "
        "gate is not honoring the store surface probe"
    )


def test_streaming_generate_structure_guard():
    """Structure/regression guard for the streaming-generate bench
    case (NOT absolute tokens/s — the ≥2x scaling at parallelism 32 is
    measured by the full bench): a tiny run must stream EVERY row
    (zero unary fallbacks — a "streaming" bench whose requests quietly
    collapse to one buffered response is lying), deliver tokens as
    progressive per-step frames (first token strictly before stream
    close), and show rows joining fused steps mid-stream (the
    continuous-batching signature)."""
    from bench import bench_streaming_generate

    # pace the decode loop so one generation deterministically spans
    # every admission round trip — at full speed stream i can finish
    # before stream i+1 even negotiates and nothing ever overlaps
    # (observed flaking at tokens=8..96 under suite load)
    tokens = 24
    out = bench_streaming_generate(
        parallelism=(1, 4), tokens=tokens, dim=16, step_delay_s=0.005
    )
    d = out["streaming_generate"]
    points = {p["parallelism"]: p for p in d["points"]}
    assert set(points) == {1, 4}, points
    # silent-unary-fallback guard: every row rode a real stream
    assert d["unary_rows"] == 0, "streams silently fell back to unary"
    assert d["streamed_rows"] == 1 + 1 + 4  # warmup + p1 + p4
    for p, pt in points.items():
        assert pt["tokens"] == tokens * p, pt
        # progressive delivery: every stream saw its first token
        # before its close event (unary would deliver nothing here)
        assert pt["progressive_streams"] == p, pt
    # continuous batching actually fused concurrent rows
    assert points[4]["max_fused"] >= 2, (
        f"4 concurrent generations never fused "
        f"(max_fused {points[4]['max_fused']}): decode loop serialized"
    )
    assert points[4]["mid_stream_joins"] >= 1, points[4]
    assert "speedup_p4_vs_p1" in d


def test_disagg_serving_structure_guard():
    """Structure guard for bench_disagg_serving (NOT absolute tokens/s
    — the full bench measures that at parallelism 32): a tiny run must
    produce both comparison lanes per point, complete EVERY session in
    the migration-under-load segment with prefill executed exactly once
    per session (migration reuses the cached KV — serving_prefill_reuse
    must advance at least once), and ride a real token stream on the
    wire segment (zero unary fallbacks — a "streamed front" that
    quietly buffers one unary response is lying)."""
    from bench import bench_disagg_serving

    tokens = 12
    out = bench_disagg_serving(
        parallelism=(1, 4), tokens=tokens, dim=12, n_layers=2,
        migrate_tokens=24, migrate_sessions=2,
        migrate_step_delay_s=0.01,
    )
    d = out["disagg_serving"]
    points = {p["parallelism"]: p for p in d["points"]}
    assert set(points) == {1, 4}, points
    for pt in points.values():
        assert pt["disagg_tokens_per_s"] > 0, pt
        assert pt["mono_tokens_per_s"] > 0, pt
        assert pt["disagg_ttft_ms_median"] > 0, pt
    mig = d["migration"]
    # every session completed, nothing ever recomputed prefill
    assert mig["completed"] == mig["sessions"], mig
    assert mig["prefill_executions_max"] == 1, (
        f"migration recomputed prefill: {mig}"
    )
    assert mig["migrations_live"] >= 1, mig
    # the KV-reuse counter advanced for the re-homed legs
    assert d["prefill_reuse"] >= 1, d
    # wire segment: a real stream, never the unary fallback
    assert d["rpc_front"]["frames"] == tokens, d["rpc_front"]
    assert d["rpc_front"]["streamed_rows"] == 1, d["rpc_front"]
    assert d["unary_fallback_rows"] == 0, (
        "the streamed token front silently fell back to unary"
    )


def test_device_witness_bench_structure_guard():
    """Structure guard for bench_device_witness_overhead (NOT the
    armed percentage — short segments under suite load swing wildly;
    the armed lane has no budget anyway): a tiny run must produce the
    headline keys, hand the global witness back as it found it, PROVE the
    armed segments really ran under the witness (armed_manifested_pulls
    counts the decode loop's per-step scoped pulls — a silently-skipped
    witness lane reads 0 here and fails loudly), record zero
    violations, and keep the disarmed no-op scope — the only thing
    instrumented code pays on every un-witnessed run — under its <1%
    per-step budget (measured ~0.06% on this host)."""
    from bench import bench_device_witness_overhead
    from incubator_brpc_tpu.analysis import device_witness

    was_armed = device_witness.enabled()
    out = bench_device_witness_overhead(rows=4, tokens=16, dim=16, pairs=2)
    # the bench toggles the GLOBAL witness: under `make witness-device`
    # it must hand the armed lane back exactly as it found it
    assert device_witness.enabled() == was_armed, (
        "bench did not restore the witness state"
    )
    d = out["device_witness_overhead"]
    for key in (
        "decode_tok_s_witness_off", "decode_tok_s_witness_armed",
        "armed_overhead_pct", "disarmed_scope_ns",
        "disarmed_scope_pct_of_step", "armed_manifested_pulls",
        "armed_violations",
    ):
        assert key in d, d
    assert d["decode_tok_s_witness_off"] > 0, d
    assert d["decode_tok_s_witness_armed"] > 0, d
    assert d["armed_manifested_pulls"] > 0, (
        "armed segments recorded zero manifested pulls: the witness "
        "lane was silently skipped"
    )
    assert d["armed_violations"] == 0, d
    assert d["disarmed_scope_pct_of_step"] < 1.0, d


def test_hbm_cache_bench_structure_guard():
    """Structure guard for bench_hbm_cache (NOT absolute qps or the
    <1% disabled budget — those come from the full bench on a quiet
    host): a tiny run must PROVE the three claims the cache tier rides
    on.  (1) Residency: the witness-armed device hit segment recorded
    ZERO cache.host-spill pulls while the one armed TCP GET manifested
    at least one — so a silently-dead witness cannot fake the zero.
    (2) Locality: healthy cluster traffic stayed >=90% in the ICI
    neighborhood, and killing the local replica actually crossed to
    the survivor (picks_remote > 0) while still serving every key.
    (3) The disabled-overhead triplet produced its drift-cancelled
    fields against the plain KVRedisService baseline."""
    from bench import bench_hbm_cache
    from incubator_brpc_tpu.analysis import device_witness

    was_armed = device_witness.enabled()
    out = bench_hbm_cache(
        sizes=(4096,), seg_calls=30, proof_calls=8, cluster_keys=6,
        cluster_calls=30, pairs=2, overhead_calls=40,
    )
    assert device_witness.enabled() == was_armed, (
        "bench did not restore the witness state"
    )
    d = out["hbm_cache"]
    assert d["witness_armed"] is True
    assert d["hit_path_spill_pulls"] == 0, (
        "device hit path pulled through cache.host-spill: residency lost"
    )
    assert d["spill_manifested_pulls"] > 0, (
        "armed TCP spill recorded zero pulls: the witness lane was "
        "silently skipped"
    )
    assert d["hit_path_violations"] == 0, d
    p = d["get_qps"]["4096"]
    assert p["device_hit_qps"] > 0 and p["host_hit_qps"] > 0
    assert d["device_miss_qps"] > 0 and d["host_miss_qps"] > 0
    c = d["cluster"]
    assert c["locality_fraction"] >= 0.9, c
    assert c["picks_remote_after_kill"] > 0, c
    assert c["spill_hits"] == 30, c  # every spilled GET still served
    o = d["cache_disabled_overhead"]
    assert {
        "get_4kb_qps_cache_disabled", "get_4kb_qps_plain_kv",
        "overhead_pct", "overhead_pct_segments",
    } <= set(o)
    assert o["get_4kb_qps_cache_disabled"] > 0
    assert o["get_4kb_qps_plain_kv"] > 0
    assert len(o["overhead_pct_segments"]) == 2


def test_overload_storm_bench_structure_guard():
    """Structure guard for bench_overload_storm (NOT absolute qps —
    the acceptance numbers come from the full bench): a tiny run must
    produce per-tier stats for both phases, land its sheds on the bulk
    tier (weighted shedding — interactive sheds would mean the tiers
    are inverted or ignored), complete every hedged call exactly once,
    cut the hedged tail measurably below the slow-replica window, and
    cancel hedge losers before device work on the slow replica."""
    from bench import bench_overload_storm

    out = bench_overload_storm(
        replicas=2, bulk_threads=3, interactive_threads=2,
        calls_per_thread=5, bulk_sleep_us=40_000, hedge_calls=10,
    )
    s = out["overload_storm"]
    for phase in ("storm_off", "storm_on"):
        for tier in ("interactive", "bulk"):
            stats = s[phase][tier]
            assert {"completed", "qps", "p50_ms", "p99_ms"} <= set(stats)
        assert s[phase]["interactive"]["completed"] > 0, s[phase]
    # weighted shedding: whatever shed, shed bulk-first (≥90%)
    total_shed = sum(s["storm_on"]["sheds_by_tier"].values())
    if total_shed:
        assert s["bulk_shed_fraction_storm_on"] >= 0.9, s["storm_on"]
    h = s["hedging"]
    # exactly-once completion for every hedged call
    assert h["hedged"]["completed"] == 10, h
    assert h["no_hedge"]["completed"] == 10, h
    # hedging measurably cuts the tail vs the slow replica's window
    assert h["hedged"]["p99_ms"] < h["no_hedge"]["p99_ms"], h
    # loser cancellation: the slow replica executed fewer (ideally 0)
    # rows once hedging raced it
    assert (
        h["slow_replica_rows_executed_hedged"]
        < h["slow_replica_rows_executed_no_hedge"]
    ), h


def test_sharded_ps_structure_guard():
    """Structure guard for the sharded-PS bench (NOT absolute qps —
    the >=0.8x-of-unsharded acceptance is a pod property; this guard
    pins the PROOF counters): every sharded point must show the fused
    lowering actually engaged — fused_executions == batches (ONE
    device execution per batch, not N) and collective_merges ==
    batches (ONE merge per batch) — so a silently-unsharded fallback
    fails loudly; the max-servable sweep must place a >=2x-single-chip
    W within the per-chip budget and serve it."""
    import jax

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs >=4 devices (conftest provides 8 virtual)")
    from bench import _bench_sharded_ps_impl

    out = _bench_sharded_ps_impl(
        shards=(1, 4), parallelism=(6,), duration_s=0.4, dim=256,
        overhead_pairs=2, overhead_calls=40,
    )
    points = {p["shards"]: p for p in out["points"]}
    assert set(points) == {1, 4}, points
    un, sh = points[1], points[4]
    assert un["ok"] > 0 and sh["ok"] > 0
    # the unsharded baseline never touches the sharded kernel
    assert un["sharded"] is False and un["collective_merges"] == 0
    # the sharded point PROVES the fused lowering by step log
    assert sh["sharded"] is True
    assert sh["batches"] >= 1
    assert sh["fused_executions"] == sh["batches"], (
        f"sharded path did not fuse: {sh['fused_executions']} executions "
        f"for {sh['batches']} batches (silently-unsharded fallback?)"
    )
    assert sh["collective_merges"] == sh["batches"], sh
    assert sh["observed_max_batch"] >= 2, (
        "6 concurrent callers never coalesced — batcher silently disabled"
    )
    assert "speedup_vs_unsharded" in sh
    # HBM-ceiling sweep: >=2x single-chip d, placed within budget, served
    ms = out["max_servable"]
    assert ms["ratio_vs_single_chip"] >= 2.0, ms
    assert all(e["fits_budget"] and e["served"] for e in ms["sweep"]), ms
    assert "overhead_pct" in out["sharded_unsharded_overhead"]


def test_cluster_scrape_bench_structure_guard():
    """Structure guard for bench_cluster_scrape_overhead (NOT the <1%
    budget — that acceptance number comes from the full bench on a
    quiet host; this one-core CI host swings more than the budget): a
    tiny run must actually scrape while ON (scrape_rounds > 0) and
    produce the OFF/ON/OFF drift-cancelled fields."""
    from bench import bench_cluster_scrape_overhead

    out = bench_cluster_scrape_overhead(seg_calls=60, pairs=2)
    s = out["cluster_scrape_overhead"]
    assert {
        "echo_1kb_qps_scrape_on", "echo_1kb_qps_scrape_off",
        "overhead_pct", "overhead_pct_segments", "scrape_rounds",
    } <= set(s)
    assert s["scrape_rounds"] > 0, "ON segments never scraped"
    assert len(s["overhead_pct_segments"]) == 2
    assert s["echo_1kb_qps_scrape_on"] > 0
    assert s["echo_1kb_qps_scrape_off"] > 0


def test_cluster_stitch_and_merge_invariants():
    """The two cluster-plane invariants the scrape bench rides on,
    pinned synthetically (no sockets, no timing): a stitched fan-out
    renders ONE tree at depth >= 3 with a residual per leg, and merged
    percentiles have error == 0 against the pooled samples."""
    from incubator_brpc_tpu.metrics.latency_recorder import (
        LatencyRecorder,
        merge_latency_snapshots,
        percentile_from_buckets,
    )
    from incubator_brpc_tpu.observability import cluster
    from incubator_brpc_tpu.observability.span import Span

    # --- stitched depth >= 3 over a synthetic 2-leg fan-out ---------
    tid = 0x5117C4
    peers = ["10.0.0.1:8000", "10.0.0.2:8000"]

    def client_span(span_id, parent, remote, start, end):
        s = Span("client", "Ps", "Forward")
        s.trace_id, s.span_id, s.parent_span_id = tid, span_id, parent
        s.start_us, s.end_us, s.remote_side = start, end, remote
        return s

    local = [
        client_span(1, 0, "", 1_000, 50_000),           # fan-out root
        client_span(2, 1, peers[0], 1_500, 21_500),     # leg latency 20ms
        client_span(3, 1, peers[1], 1_500, 31_500),     # leg latency 30ms
    ]

    def fetch(ep, trace_id, timeout, retries, retry_delay_s):
        leg = 2 if ep == peers[0] else 3
        return [
            cluster.span_from_dict(
                {
                    "trace_id": f"{trace_id:x}", "span_id": f"{leg * 16:x}",
                    "parent_span_id": f"{leg:x}", "kind": "server",
                    "service": "Ps", "method": "Forward",
                    "start_us": 2_000, "end_us": 7_000,   # server 5ms
                    "phases": {"received_us": 2_000, "sent_us": 7_000},
                },
                ep,
            )
        ]

    text = cluster.render_stitched(
        tid, db=cluster._StitchDB(local), fetch=fetch
    )
    assert text is not None
    lines = text.splitlines()
    assert sum(1 for l in lines if l.startswith("+")) == 1   # ONE tree
    assert sum(1 for l in lines if l.startswith("  +")) == 2
    assert sum(1 for l in lines if l.startswith("    +")) == 2  # depth 3
    residuals = [l for l in lines if "wire+queue residual=" in l]
    assert len(residuals) == 2
    # residual = client leg latency - server elapsed, per leg
    assert any("residual=15000us" in l for l in residuals), residuals
    assert any("residual=25000us" in l for l in residuals), residuals
    for ep in peers:
        assert f"@{ep}" in text

    # --- merged percentile error == 0 vs pooled ---------------------
    a, b, pooled = LatencyRecorder(), LatencyRecorder(), LatencyRecorder()
    for i in range(150):
        v = 40 + 97 * i
        (a if i % 2 else b).update(v)
        pooled.update(v)
    merged = merge_latency_snapshots(
        [a.mergeable_snapshot(), b.mergeable_snapshot()]
    )
    for ratio in (0.5, 0.9, 0.99):
        err = abs(
            percentile_from_buckets(merged["buckets"], ratio)
            - pooled.latency_percentile(ratio)
        )
        assert err == 0, f"p{ratio}: merged differs from pooled by {err}"


def test_resharding_bench_structure_guard():
    """Structure guard for bench_resharding (NOT absolute qps — the
    zero-downtime acceptance is a step-log property): a tiny live
    2→4 migration under Get/Put/Forward load must reach DONE with
    exactly one epoch bump, move exactly the planner's scheme delta
    (no spurious copies, no misses), verify every range (zero
    checksum failures without chaos), and complete every concurrent
    call with an ERPC-family error code or success — a stale-route
    EINTERNAL here means the cutover leaked a mixed-scheme fan-out."""
    from bench import bench_resharding
    from incubator_brpc_tpu import errors as _errors

    out = bench_resharding(
        n_keys=24, dim=16, load_threads=2, phase_calls=20,
    )
    r = out["resharding"]
    m = r["migration"]
    assert m["completed"], m
    assert m["epoch"] == 1, m
    assert m["keys_moved"] == m["planner_scheme_delta"], m
    assert m["checksum_failures"] == 0, m
    for phase in ("pre", "during", "post"):
        stats = r["phases"][phase]
        assert stats["calls"] > 0, r["phases"]
        assert {"qps", "p50_ms", "p99_ms", "errors"} <= set(stats)
    # every error code seen under load must be a known ERPC code —
    # never EINTERNAL (stale route) or a raw exception surrogate
    erpc = {
        v for k, v in vars(_errors).items()
        if k.isupper() and isinstance(v, int)
    } - {_errors.EINTERNAL}
    for code, count in r["errors_by_code"].items():
        assert int(code) in erpc, (code, count)


def test_profiler_overhead_bench_structure_guard():
    """Structure guard for bench_profiler_overhead (NOT the <1%
    acceptance — that comes from the full bench on a quiet host): a
    tiny run must produce both OFF/ON/OFF triplets (echo + decode),
    positive rates on every lane, the drift-cancelled per-segment
    deltas, and — the part a structure guard CAN pin — hand all three
    profiler flags back armed and the HBM ledger balanced across the
    flips (a row admitted ON and finished OFF nets zero; an unbalanced
    release would go negative here)."""
    from bench import bench_profiler_overhead
    from incubator_brpc_tpu.observability import profiling
    from incubator_brpc_tpu.utils.flags import get_flag

    decode_acct = profiling.hbm_account("decode.rows")
    b0 = decode_acct.live_bytes()
    out = bench_profiler_overhead(
        payload=256, seg_calls=40, rows=2, tokens=8, dim=8, pairs=2
    )
    for f in ("profiler_hbm_enabled", "profiler_device_enabled",
              "profiler_occupancy_enabled"):
        assert get_flag(f) is True, f"bench left {f} disarmed"
    d = out["profiler_overhead"]
    for key in (
        "echo_1kb_qps_profilers_on", "echo_1kb_qps_profilers_off",
        "echo_overhead_pct", "echo_overhead_pct_segments",
        "decode_tok_s_profilers_on", "decode_tok_s_profilers_off",
        "decode_overhead_pct", "decode_overhead_pct_segments",
    ):
        assert key in d, d
    assert d["echo_1kb_qps_profilers_on"] > 0, d
    assert d["echo_1kb_qps_profilers_off"] > 0, d
    assert d["decode_tok_s_profilers_on"] > 0, d
    assert d["decode_tok_s_profilers_off"] > 0, d
    assert len(d["echo_overhead_pct_segments"]) == 2, d
    assert len(d["decode_overhead_pct_segments"]) == 2, d
    assert decode_acct.live_bytes() == b0, (
        "decode.rows ledger unbalanced after ON/OFF flips: "
        f"{decode_acct.live_bytes() - b0} bytes net charge"
    )


def test_replicated_ps_bench_structure_guard():
    """Structure guard for bench_replicated_ps (NOT absolute qps): a
    tiny run must produce the RF=1 OFF/ON/OFF triplet (the collapse
    keeps the disabled path free — bounded loosely here, ≈0% comes
    from the full bench on a quiet host), an RF=3 steady segment in
    which every Put is a QUORUM write and the leader never changes (a
    silently-unreplicated or lease-flapping run fails loudly), and the
    hedged-tail segment with a real cut: hedges fired and the hedged
    p99 beat the no-hedge p99 against the same slowed replica."""
    from bench import bench_replicated_ps

    out = bench_replicated_ps(
        n_keys=12, rf1_calls=40, rf3_calls=40, hedged_calls=24,
        slow_delay_us=50_000,
    )
    assert "replicated_ps" in out, out  # no swallowed-error shape
    r = out["replicated_ps"]
    trip = r["rf1_triplet"]
    for seg in ("off1", "on", "off2"):
        assert trip[seg]["calls"] > 0, trip
        assert trip[seg]["errors"] == 0, trip
        assert {"qps", "p50_ms", "p99_ms"} <= set(trip[seg])
    # noise-tolerant bound at smoke scale; the ≈0% triplet acceptance
    # belongs to the full bench run
    assert trip["overhead_pct"] < 25.0, trip
    assert r["rf3"]["calls"] > 0 and r["rf3"]["errors"] == 0, r["rf3"]
    assert r["quorum_writes"] >= r["puts"] > 0, r
    assert r["steady_leader_changes"] == 0, r
    h = r["hedged_tail"]
    assert h["hedged_reads"] > 0, h
    assert h["p99_ms_hedged"] < h["p99_ms_nohedge"], h
