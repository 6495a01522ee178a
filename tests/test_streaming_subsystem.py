"""Streaming RPC subsystem (incubator_brpc_tpu/streaming/): wire-frame
parsing, per-direction stream ids, StreamWait flow control, feedback
batching, half-close, idle timeout, message segmentation, the
stream.frame chaos site, and the rpc_stream_* observability surface.
(Reference patterns: brpc_streaming_rpc_unittest + stream.h:50-130.)"""

import struct
import threading
import time

from incubator_brpc_tpu import errors
from incubator_brpc_tpu.chaos import FaultPlan
from incubator_brpc_tpu.chaos import injector as chaos_injector
from incubator_brpc_tpu.chaos.harness import RecoveryHarness
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.models.streaming_echo import StreamingEchoService
from incubator_brpc_tpu.protocols import ParseError
from incubator_brpc_tpu.protocols import streaming as wire
from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest, EchoResponse
from incubator_brpc_tpu.server.server import Server
from incubator_brpc_tpu.server.service import Service, ServiceStub, rpc_method
from incubator_brpc_tpu.streaming import observe
from incubator_brpc_tpu.streaming.stream import Stream, StreamHandler, StreamOptions
from incubator_brpc_tpu.utils.iobuf import IOBuf


class _FakeSock:
    is_server_side = True
    failed = False

    def __init__(self):
        self.stream_map = {}
        self.written = []
        self.remote = "fake:0"

    def write(self, buf, **kw):
        self.written.append(buf.to_bytes())
        return 0


# ---- wire parser (satellite: magic-prefix precedence fix + fuzz) -----------


def _parse(data: bytes):
    return wire.parse(IOBuf(data), _FakeSock(), False)


def test_parse_partial_magic_prefix_waits():
    # the old `A and B or C` precedence expression misrouted these
    for prefix in (b"T", b"TS", b"TST", b"TSTM"):
        r = _parse(prefix)
        assert r.error == ParseError.NOT_ENOUGH_DATA, prefix


def test_parse_non_magic_tries_others():
    for garbage in (b"X", b"TX", b"TSX", b"XSTM", b"HTTP"):
        r = _parse(garbage)
        assert r.error == ParseError.TRY_OTHERS, garbage


def test_parse_truncated_header_with_magic_waits():
    r = _parse(wire.MAGIC + b"\x00" * 5)  # magic + partial header
    assert r.error == ParseError.NOT_ENOUGH_DATA


def test_parse_bad_type_byte_kills_connection():
    hdr = wire.MAGIC + struct.pack(">QBI", 1, 0x7F, 0)
    assert _parse(hdr).error == ParseError.BAD_FORMAT


def test_parse_oversized_length_kills_connection():
    hdr = wire.MAGIC + struct.pack(">QBI", 1, wire.FRAME_DATA, 0xFFFFFFFF)
    assert _parse(hdr).error == ParseError.BAD_FORMAT


def test_parse_roundtrip_all_frame_types():
    for ftype in sorted(wire._VALID_FRAME_TYPES):
        buf = wire.pack_frame(7, ftype, IOBuf(b"pay"))
        r = _parse(buf.to_bytes())
        assert r.error == ParseError.OK
        assert r.message.stream_id == 7
        assert r.message.frame_type == ftype
        assert r.message.payload.to_bytes() == b"pay"


def test_unknown_stream_data_part_gets_rst():
    sock = _FakeSock()
    frame = wire.StreamFrame(99, wire.FRAME_DATA_PART, IOBuf(b"x"))
    wire.process_frame(frame, sock)
    assert len(sock.written) == 1
    r = wire.parse(IOBuf(sock.written[0]), _FakeSock(), False)
    assert r.message.frame_type == wire.FRAME_RST
    assert r.message.stream_id == 99


# ---- stream-id namespaces (satellite: odd/even, the h2 discipline) ---------


def test_stream_ids_namespaced_per_direction():
    c1 = Stream(StreamOptions(), is_server=False)
    c2 = Stream(StreamOptions(), is_server=False)
    s1 = Stream(StreamOptions(), is_server=True)
    s2 = Stream(StreamOptions(), is_server=True)
    assert c1.stream_id % 2 == 1 and c2.stream_id % 2 == 1
    assert s1.stream_id % 2 == 0 and s2.stream_id % 2 == 0
    assert c2.stream_id > c1.stream_id
    assert s2.stream_id > s1.stream_id


def test_stream_id_collision_regression():
    """Two peers on one connection each minting their FIRST stream
    must not collide (independent count(1) sequences both minted 1
    before the parity split): registering both on one socket's
    stream_map keeps both routable."""
    sock = _FakeSock()
    client = Stream(StreamOptions(), is_server=False)
    server = Stream(StreamOptions(), is_server=True)
    sock.stream_map[client.stream_id] = client
    sock.stream_map[server.stream_id] = server
    assert len(sock.stream_map) == 2
    assert sock.stream_map[client.stream_id] is client
    assert sock.stream_map[server.stream_id] is server


# ---- live-server fixtures ---------------------------------------------------


class Collect(StreamHandler):
    def __init__(self):
        self.chunks = []
        self.closed = threading.Event()
        self.half_closed = threading.Event()
        self.failures = []
        self.got = threading.Condition()

    def on_received_messages(self, stream, messages):
        with self.got:
            self.chunks.extend(m.to_bytes() for m in messages)
            self.got.notify_all()

    def on_closed(self, stream):
        self.closed.set()

    def on_half_close(self, stream):
        self.half_closed.set()

    def on_failed(self, stream, code, text):
        self.failures.append((code, text))

    def wait_chunks(self, n, timeout=15):
        with self.got:
            return self.got.wait_for(lambda: len(self.chunks) >= n, timeout)


class _SlowEcho(StreamHandler):
    """Server-side consumer that sleeps per message batch — the slow
    consumer that must exert backpressure on the writer."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def on_received_messages(self, stream, messages):
        time.sleep(self.delay_s)
        for m in messages:
            stream.write(m)


class SlowStreamService(Service):
    SERVICE_NAME = "SlowStreamService"
    consume_delay_s = 0.05

    @rpc_method(EchoRequest, EchoResponse)
    def Start(self, controller, request, response, done):
        Stream.accept(controller, _SlowEcho(self.consume_delay_s))
        response.message = "ok"
        done()


class HalfCloseEchoService(Service):
    """Echoes each chunk; on the peer's half-close, writes a final
    summary then half-closes its own side."""

    SERVICE_NAME = "HalfCloseEchoService"

    def __init__(self):
        self.server_streams = []

    @rpc_method(EchoRequest, EchoResponse)
    def Start(self, controller, request, response, done):
        svc = self

        class _H(StreamHandler):
            def __init__(self):
                self.n = 0

            def on_received_messages(self, stream, messages):
                self.n += len(messages)
                for m in messages:
                    stream.write(m)

            def on_half_close(self, stream, _h=None):
                stream.write(f"summary:{self.n}".encode())
                stream.close_write()

        stream = Stream.accept(controller, _H())
        svc.server_streams.append(stream)
        response.message = "ok"
        done()


def start_server(service):
    srv = Server()
    srv.add_service(service)
    assert srv.start(0) == 0
    return srv


def make_channel(port, **kw):
    kw.setdefault("timeout_ms", 5000)
    ch = Channel(ChannelOptions(**kw))
    assert ch.init(f"127.0.0.1:{port}") == 0
    return ch


def _negotiate(srv, service_cls, method, handler, options=None):
    ch = make_channel(srv.port)
    stub = ServiceStub(ch, service_cls)
    ctrl = Controller()
    stream = Stream.create(ctrl, handler, options)
    getattr(stub, method)(ctrl, EchoRequest(message="start"))
    assert not ctrl.failed(), ctrl.error_text()
    assert stream.wait_established(5)
    return ch, stream


# ---- flow control -----------------------------------------------------------


def test_writer_blocks_on_slow_consumer_and_resumes():
    """With max_buf_size set and a slow consumer the writer measurably
    blocks (StreamWait), resumes on FEEDBACK, and everything arrives —
    no unbounded backlog, no deadlock (acceptance criterion)."""
    srv = start_server(SlowStreamService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, SlowStreamService, "Start", collect,
            StreamOptions(max_buf_size=64 * 1024),
        )
        chunk = b"x" * 32 * 1024
        for _ in range(12):  # 384KB through a 64KB window
            assert stream.write(IOBuf(chunk), timeout=30) == 0
            # the writer-side view of the peer backlog stays bounded
            assert stream.unconsumed() <= 64 * 1024
        assert collect.wait_chunks(12, timeout=30), len(collect.chunks)
        assert sum(len(c) for c in collect.chunks) == 12 * 32 * 1024
        # blocked time was actually recorded (the writer did wait)
        assert stream.writer_blocked_ns > 0
        stream.close()
        ch.close()
    finally:
        srv.stop()


def test_feedback_batching_min_buf_size():
    """A receiver with min_buf_size batches consumed-bytes feedback:
    far fewer FEEDBACK frames come back than messages went out."""
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        # this side both writes AND consumes the echo; its min_buf
        # batches the feedback IT sends. The peer's (server's) options
        # are defaults, so count the feedback frames WE receive from
        # the server: server has min_buf 0 → per-batch feedback. So
        # instead drive the assertion from the server side via our own
        # batching: our feedback to the server is what min_buf bounds.
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect,
            StreamOptions(min_buf_size=256 * 1024),
        )
        for i in range(16):
            assert stream.write(b"y" * 8192) == 0
        assert collect.wait_chunks(16)
        # we consumed 16 echoed messages (128KB) but stayed under the
        # 256KB feedback threshold: at most the close-time flush went
        # out, not 16 per-message FEEDBACK frames
        assert stream.consumed_bytes == 16 * 8192
        fb_frames = stream.frames_sent - 16  # minus the DATA frames
        assert fb_frames <= 1, f"feedback not batched: {fb_frames} frames"
        stream.close()
        ch.close()
    finally:
        srv.stop()


def test_segmented_large_message_survives_small_window():
    """One message larger than BOTH the wire chunk and max_buf_size
    streams through DATA_PART segmentation and arrives as ONE message
    (boundaries preserved), without deadlocking the window."""
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect,
            StreamOptions(max_buf_size=128 * 1024, write_chunk_bytes=64 * 1024),
        )
        payload = bytes(range(256)) * 4096  # 1MB, patterned
        assert stream.write(IOBuf(payload), timeout=30) == 0
        assert collect.wait_chunks(1, timeout=30)
        assert len(collect.chunks) == 1, "segmentation broke message boundaries"
        assert collect.chunks[0] == payload
        stream.close()
        ch.close()
    finally:
        srv.stop()


# ---- half-close state machine ----------------------------------------------


def test_half_close_handshake():
    srv = start_server(HalfCloseEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(srv, HalfCloseEchoService, "Start", collect)
        for i in range(3):
            assert stream.write(f"m{i}".encode()) == 0
        assert collect.wait_chunks(3)
        stream.close_write()  # we are done writing; still reading
        assert stream.write(b"nope") == errors.ECLOSE
        # server answers the half-close with a summary, then
        # half-closes its side → both directions done → full close
        assert collect.wait_chunks(4), collect.chunks
        assert collect.chunks[3] == b"summary:3"
        assert collect.closed.wait(5)
        assert stream.closed
        ch.close()
    finally:
        srv.stop()


def test_idle_timeout_fails_stream():
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect,
            StreamOptions(idle_timeout_s=0.4),
        )
        # no traffic at all: the idle timer must fail the stream
        assert collect.closed.wait(5), "idle timeout never fired"
        assert stream.failed_code == errors.ERPCTIMEDOUT
        assert collect.failures and collect.failures[0][0] == errors.ERPCTIMEDOUT
        assert stream.write(b"late") != 0
        ch.close()
    finally:
        srv.stop()


# ---- chaos: stream.frame ----------------------------------------------------


def test_chaos_dropped_feedback_cannot_deadlock_blocked_writer():
    """Every FEEDBACK frame is dropped; the writer fills max_buf_size
    and blocks.  The idle-timeout path must release it in bounded time
    with an ERPC code — proven under the RecoveryHarness invariants
    (bounded wall clock, whitelisted codes, clean controller pool)."""
    plan = FaultPlan.from_dict({
        "name": "feedback-blackhole",
        "seed": 42,
        "specs": [{
            "site": "stream.frame",
            "action": "drop",
            "probability": 1.0,
            "match": {"direction": "feedback"},
        }],
    })
    srv = start_server(SlowStreamService())
    try:
        def workload(h):
            collect = Collect()
            ch, stream = _negotiate(
                srv, SlowStreamService, "Start", collect,
                StreamOptions(max_buf_size=32 * 1024, idle_timeout_s=1.0),
            )
            rc = 0
            for _ in range(8):  # 256KB into a 32KB window: must block
                rc = stream.write(IOBuf(b"z" * 32 * 1024), timeout=10)
                if rc != 0:
                    break
            h.record_error(rc)
            ch.close()
            return rc

        report = RecoveryHarness(plan, wall_clock_s=20.0).run_or_raise(workload)
        # the blocked writer came back with an error, not a deadlock
        assert report.workload_result in (
            errors.ERPCTIMEDOUT, errors.ECLOSE,
        ), report.workload_result
        assert report.hits.get("stream.frame", {}).get("drop", 0) >= 1
    finally:
        srv.stop()


def test_chaos_stream_reset_spares_the_socket():
    """stream.frame reset kills ONE stream; the shared connection (and
    a follow-up RPC on it) stays healthy."""
    srv = start_server(StreamingEchoService())
    # peer-match the CLIENT's egress only: the echo server's own frames
    # traverse the same site in this process, and letting both advance
    # the spec counter would make the firing thread nondeterministic
    plan = FaultPlan.from_dict({
        "name": "stream-reset",
        "seed": 7,
        "specs": [{
            "site": "stream.frame",
            "action": "reset",
            "every_nth": 3,
            "match": {"direction": "data", "peer": f"127.0.0.1:{srv.port}"},
        }],
    })
    try:
        collect = Collect()
        ch, stream = _negotiate(srv, StreamingEchoService, "StartStream", collect)
        chaos_injector.arm(plan)
        try:
            rc = 0
            for i in range(6):
                rc = stream.write(f"c{i}".encode())
                if rc:
                    break
            assert rc == errors.ECLOSE  # the injected stream reset
        finally:
            chaos_injector.disarm()
        assert collect.closed.wait(5)
        # the socket survived: a normal RPC on the same channel works
        stub = ServiceStub(ch, StreamingEchoService)
        c2 = Controller()
        collect2 = Collect()
        s2 = Stream.create(c2, collect2)
        r = stub.StartStream(c2, EchoRequest(message="again"))
        assert not c2.failed(), c2.error_text()
        assert r.message == "stream-accepted"
        assert s2.wait_established(5)
        assert s2.write(b"after-reset") == 0
        assert collect2.wait_chunks(1)
        s2.close()
        ch.close()
    finally:
        srv.stop()


def test_chaos_stream_frame_replay_is_deterministic():
    logs = []
    for _ in range(2):
        srv = start_server(StreamingEchoService())
        # client-egress only (peer match), for the same reason as the
        # reset test above: one deterministic traversal sequence
        plan_dict = {
            "name": "det", "seed": 99,
            "specs": [{"site": "stream.frame", "action": "drop",
                       "every_nth": 4,
                       "match": {"direction": "data",
                                 "peer": f"127.0.0.1:{srv.port}"}}],
        }
        try:
            collect = Collect()
            ch, stream = _negotiate(
                srv, StreamingEchoService, "StartStream", collect
            )
            chaos_injector.arm(FaultPlan.from_dict(plan_dict))
            try:
                for i in range(12):
                    stream.write(f"d{i}".encode())
                time.sleep(0.2)
            finally:
                logs.append(chaos_injector.hit_log())
                chaos_injector.disarm()
            stream.close()
            ch.close()
        finally:
            srv.stop()
    assert logs[0] == logs[1] and logs[0], logs


# ---- observability ----------------------------------------------------------


def test_stream_metrics_and_status_page():
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(srv, StreamingEchoService, "StartStream", collect)
        assert stream.write(b"metric-me") == 0
        assert collect.wait_chunks(1)
        assert observe._live_count() >= 1
        by_method = observe.streams_by_method()
        assert "StreamingEchoService.StartStream" in by_method
        # pick OUR stream's row: the registry is process-global and a
        # just-closed stream from an earlier test deregisters
        # asynchronously, so [0] can be a stale frames_sent=0 row
        row = next(
            r for r in by_method["StreamingEchoService.StartStream"]
            if r["id"] == stream.stream_id
        )
        assert row["frames_sent"] >= 1

        import urllib.request

        status = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/status", timeout=5
        ).read().decode()
        assert "streams:" in status
        assert "StreamingEchoService.StartStream" in status
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert "rpc_stream_live" in metrics
        assert "rpc_stream_blocked_writers" in metrics
        stream.close()
        # deregistered on close
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and stream.stream_id in {
            s.stream_id for s in observe.live()
        }:
            time.sleep(0.01)
        assert stream.stream_id not in {s.stream_id for s in observe.live()}
        ch.close()
    finally:
        srv.stop()


def test_stream_rpcz_span_joined_to_rpc_trace():
    from incubator_brpc_tpu.utils.flags import get_flag, set_flag

    prev = get_flag("rpcz_enabled", True)
    set_flag("rpcz_enabled", True)
    try:
        srv = start_server(StreamingEchoService())
        try:
            collect = Collect()
            ch, stream = _negotiate(
                srv, StreamingEchoService, "StartStream", collect
            )
            assert stream._span is not None
            trace_id = stream._span.trace_id
            assert trace_id != 0
            assert stream.write(b"traced") == 0
            assert collect.wait_chunks(1)
            stream.close()
            assert stream._span is None  # closed exactly once
            ch.close()
        finally:
            srv.stop()
    finally:
        set_flag("rpcz_enabled", prev)


# ---- streams over the ICI fabric (device payloads) --------------------------


def test_stream_over_ici_device_payload():
    """The transport half of the tentpole: a stream negotiated over an
    ici:// connection moves an HBM tensor through the fabric's chunked
    staging-ring pipeline (frames never split device payloads here —
    the fabric owns that), and the frames round-trip bit-exact."""
    import jax.numpy as jnp
    import numpy as np

    srv = Server()
    srv.add_service(StreamingEchoService())
    assert srv.start_ici(8, 201) == 0
    try:
        ch = Channel(ChannelOptions(timeout_ms=30000))
        assert ch.init("ici://slice8/chip201") == 0
        stub = ServiceStub(ch, StreamingEchoService)
        ctrl = Controller()
        collect = Collect()
        stream = Stream.create(ctrl, collect)
        r = stub.StartStream(ctrl, EchoRequest(message="ici-stream"))
        assert not ctrl.failed(), ctrl.error_text()
        assert r.message == "stream-accepted"
        assert stream.wait_established(10)
        x = jnp.arange(64 * 256, dtype=jnp.float32).reshape(64, 256)
        assert stream.write_device(x, timeout=30) == 0
        assert stream.write(b"host-bytes-too") == 0
        assert collect.wait_chunks(2, timeout=30), len(collect.chunks)
        assert collect.chunks[0] == np.asarray(x).tobytes()
        assert collect.chunks[1] == b"host-bytes-too"
        # a device message is ONE frame: segmentation never touched it
        assert stream.frames_sent >= 2
        stream.close()
        assert collect.closed.wait(10)
        ch.close()
    finally:
        srv.stop()


# ---- review-pass regressions ------------------------------------------------


def test_oversized_single_frame_admitted_when_window_empty():
    """A frame larger than the whole max_buf_size window (the
    unsplittable-device-payload shape) is admitted when the window is
    empty — one such message in flight at a time, instead of never
    (pre-fix: the StreamWait predicate was unsatisfiable and every
    oversized write burned its full timeout)."""
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect,
            StreamOptions(max_buf_size=64 * 1024),
        )
        import numpy as np

        big = np.arange(64 * 1024, dtype=np.float32)  # 256KB > 64KB window
        t0 = time.monotonic()
        assert stream.write_device(big, timeout=8) == 0
        assert time.monotonic() - t0 < 5, "oversized frame burned the timeout"
        assert collect.wait_chunks(1, timeout=20)
        assert collect.chunks[0] == big.tobytes()
        stream.close()
        ch.close()
    finally:
        srv.stop()


def test_default_options_large_host_write_segments_within_window():
    """With DEFAULT StreamOptions the effective chunk is clamped to
    max_buf_size (pre-fix: 4MB wire chunk > 2MB window made a 3MB
    write unsegmented AND unadmittable)."""
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect
        )
        payload = b"q" * (3 << 20)  # 3MB between window (2MB) and chunk (4MB)
        assert stream.write(IOBuf(payload), timeout=30) == 0
        assert collect.wait_chunks(1, timeout=30)
        assert len(collect.chunks) == 1 and collect.chunks[0] == payload
        stream.close()
        ch.close()
    finally:
        srv.stop()


def test_segmented_abort_mid_message_resets_stream():
    """A segmented write that dies mid-message (flow-wait timeout
    against a stalled window) RSTs the stream: the peer's half-built
    reassembly buffer can never be spliced onto a later message."""
    plan = FaultPlan.from_dict({
        "name": "fb-blackhole-abort", "seed": 3,
        "specs": [{"site": "stream.frame", "action": "drop",
                   "probability": 1.0, "match": {"direction": "feedback"}}],
    })
    srv = start_server(SlowStreamService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, SlowStreamService, "Start", collect,
            StreamOptions(max_buf_size=64 * 1024, write_chunk_bytes=32 * 1024),
        )
        chaos_injector.arm(plan)
        try:
            # 256KB through a feedback-blackholed 64KB window: some
            # chunk's flow-wait must time out mid-message
            rc = stream.write(IOBuf(b"m" * 256 * 1024), timeout=1.5)
        finally:
            chaos_injector.disarm()
        assert rc != 0
        assert stream.failed_code != 0, "mid-message abort left stream usable"
        assert collect.closed.wait(10)
        ch.close()
    finally:
        srv.stop()


def test_unknown_stream_rst_routes_back_to_writer():
    """The bounce-RST for an unknown stream is addressed with the id
    the DATA arrived under (the writer's REMOTE id — the wire has no
    source id); the writer's side must match it by remote id and fail
    the stream promptly instead of dropping the RST."""
    srv = start_server(StreamingEchoService())
    try:
        collect = Collect()
        ch, stream = _negotiate(
            srv, StreamingEchoService, "StartStream", collect
        )
        # simulate the server's stream vanishing without a wire close
        srv_stream = next(
            s for s in observe.live()
            if s.is_server and s.remote_stream_id == stream.stream_id
        )
        srv_stream._sock.stream_map.pop(srv_stream.stream_id, None)
        assert stream.write(b"into-the-void") == 0  # bounces an RST
        assert collect.closed.wait(5), "bounce-RST never routed back"
        assert stream.failed_code == errors.ECLOSE
        ch.close()
    finally:
        srv.stop()


def test_progressive_attachment_backlog_probe():
    from incubator_brpc_tpu.protocols.http import ProgressiveAttachment

    pa = ProgressiveAttachment()
    assert pa.backlog_bytes() == 0  # unbound: writes buffer

    class _S:
        _unwritten = 12345

        def _inuse_acquire(self):
            return True

        def _inuse_release(self):
            pass

        def write(self, buf, **kw):
            return 0

    pa._sock = _S()
    assert pa.backlog_bytes() == 12345
