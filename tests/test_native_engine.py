"""Native C++ engine: server fast path, Python fallback, client pool.

The engine (native/engine.cpp) is the C++ analog of the reference's
core IO loops (input_messenger.cpp:317-382, socket.cpp:1584-1790).
These tests drive it through the public framework API only."""

import threading

import pytest

from incubator_brpc_tpu import errors
from incubator_brpc_tpu import native
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.models.echo import EchoService, echo_stub
from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest
from incubator_brpc_tpu.server.server import Server, ServerOptions

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native engine: {native.unavailable_reason()}"
)


@pytest.fixture
def native_server():
    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(EchoService())
    assert srv.start(0) == 0
    assert srv._native_engine is not None, "engine did not come up"
    yield srv
    srv.stop()


def _channel(port, **kw):
    opts = ChannelOptions(connection_type="native", timeout_ms=5000, **kw)
    ch = Channel(opts)
    assert ch.init(f"127.0.0.1:{port}") == 0
    assert ch.options.connection_type == "native"
    return ch


def test_native_echo_fast_path(native_server):
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    for i in range(5):
        c = Controller()
        r = stub.Echo(c, EchoRequest(message=f"native-{i}", code=i))
        assert not c.failed(), c.error_text()
        assert r.message == f"native-{i}"
        assert r.code == i
        assert c.latency_us > 0
    ch.close()


def test_native_attachment_roundtrip(native_server):
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    c = Controller()
    c.request_attachment.append(b"A" * 70000)
    r = stub.Echo(c, EchoRequest(message="att"))
    assert not c.failed(), c.error_text()
    assert r.message == "att"
    assert c.response_attachment.to_bytes() == b"A" * 70000
    ch.close()


def test_native_fallback_fault_injection(native_server):
    """server_fail forces the C++ engine off the fast path and through
    the Python handler, which must still answer on the same conn."""
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    c = Controller()
    stub.Echo(c, EchoRequest(message="x", server_fail=errors.EINTERNAL))
    assert c.failed()
    assert c.error_code == errors.EINTERNAL
    # connection still usable for fast-path calls afterwards
    c2 = Controller()
    r2 = stub.Echo(c2, EchoRequest(message="after-fallback"))
    assert not c2.failed(), c2.error_text()
    assert r2.message == "after-fallback"
    ch.close()


def test_native_fallback_unknown_method(native_server):
    """Unknown service name → Python fallback → ENOSERVICE surfaces."""
    from incubator_brpc_tpu.server.service import MethodSpec
    from incubator_brpc_tpu.protos.echo_pb2 import EchoResponse

    ch = _channel(native_server.port)
    spec = MethodSpec("NoSuchService", "Echo", EchoRequest, EchoResponse)
    c = Controller()
    resp = EchoResponse()
    ch.call_method(spec, c, EchoRequest(message="x"), resp)
    assert c.failed()
    assert c.error_code == errors.ENOSERVICE
    ch.close()


def test_native_timeout(native_server):
    """sleep_us beyond the deadline → ERPCTIMEDOUT via the Python
    fallback path (sleep is a fault-injection field)."""
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    c = Controller()
    c.timeout_ms = 200
    stub.Echo(c, EchoRequest(message="slow", sleep_us=800_000))
    assert c.failed()
    assert c.error_code == errors.ERPCTIMEDOUT
    ch.close()


def test_native_concurrent_threads(native_server):
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    fails = []
    N, T = 800, 8

    def worker(tid):
        for i in range(N // T):
            c = Controller()
            r = stub.Echo(c, EchoRequest(message=f"t{tid}-{i}"))
            if c.failed() or r.message != f"t{tid}-{i}":
                fails.append((tid, i, c.error_text()))

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not fails, fails[:3]
    ch.close()


def test_python_client_against_native_server(native_server):
    """A default (pure-Python, single-connection) channel must interop
    with the native server — same wire format."""
    ch = Channel(ChannelOptions(timeout_ms=5000))
    assert ch.init(f"127.0.0.1:{native_server.port}") == 0
    stub = echo_stub(ch)
    c = Controller()
    r = stub.Echo(c, EchoRequest(message="py-client"))
    assert not c.failed(), c.error_text()
    assert r.message == "py-client"
    ch.close()


def test_native_client_against_python_server():
    """connection_type=native against the pure-Python server: the C
    client pool speaks standard tpu_std."""
    srv = Server()
    srv.add_service(EchoService())
    assert srv.start(0) == 0
    try:
        ch = _channel(srv.port)
        stub = echo_stub(ch)
        c = Controller()
        r = stub.Echo(c, EchoRequest(message="mixed"))
        assert not c.failed(), c.error_text()
        assert r.message == "mixed"
        ch.close()
    finally:
        srv.stop()


def test_native_server_stop_frees_port(free_port):
    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(EchoService())
    assert srv.start(free_port) == 0
    assert srv.port == free_port
    srv.stop()
    # port reusable after stop
    srv2 = Server(ServerOptions(native_engine=True))
    srv2.add_service(EchoService())
    assert srv2.start(free_port) == 0
    srv2.stop()


def test_native_client_compressed_response(native_server):
    """Handler-compressed responses decompress on the native client
    (the C layer surfaces meta.compress_type, Python decompresses)."""
    from incubator_brpc_tpu.protocols.compress import COMPRESS_TYPE_GZIP
    from incubator_brpc_tpu.server.service import Service, rpc_method
    from incubator_brpc_tpu.protos.echo_pb2 import EchoResponse

    class GzEcho(Service):
        SERVICE_NAME = "GzEchoService"

        @rpc_method(EchoRequest, EchoResponse)
        def Echo(self, controller, request, response, done):
            response.message = request.message
            controller.response_compress_type = COMPRESS_TYPE_GZIP
            done()

    assert native_server.add_service(GzEcho()) == 0
    ch = _channel(native_server.port)
    from incubator_brpc_tpu.server.service import ServiceStub

    stub = ServiceStub(ch, GzEcho)
    c = Controller()
    r = stub.Echo(c, EchoRequest(message="compress-me " * 50))
    assert not c.failed(), c.error_text()
    assert r.message == "compress-me " * 50
    ch.close()


def test_native_async_done_callback(native_server):
    """Async RPC over the mux reactor: done runs, response filled."""
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    evs = []
    ctrls = []
    for i in range(20):
        ev = threading.Event()
        c = Controller()
        r = stub.Echo(c, EchoRequest(message=f"async-{i}"), done=ev.set)
        evs.append((ev, c, r, f"async-{i}"))
        ctrls.append(c)
    for ev, c, r, want in evs:
        assert ev.wait(5), "done never ran"
        assert not c.failed(), c.error_text()
        assert r.message == want
        assert c.latency_us > 0
    ch.close()


def test_native_async_timeout(native_server):
    ch = _channel(native_server.port)
    stub = echo_stub(ch)
    ev = threading.Event()
    c = Controller()
    c.timeout_ms = 150
    stub.Echo(c, EchoRequest(message="slow", sleep_us=900_000), done=ev.set)
    assert ev.wait(5)
    assert c.failed()
    assert c.error_code == errors.ERPCTIMEDOUT
    ch.close()


def test_native_press_tool(native_server):
    """tools/rpc_press --native path: native load gen vs native server."""
    from incubator_brpc_tpu.tools.rpc_press import press_native

    out = []
    r = press_native(
        f"127.0.0.1:{native_server.port}", concurrency=2,
        duration_s=0.5, payload_len=512, report=out.append,
    )
    assert r is not None and r["ok"] > 0 and r["failed"] == 0, (r, out)
    assert r["p50_us"] > 0


def test_native_engine_over_uds(tmp_path):
    """Native engine on a unix-domain socket (UDS is first-class in the
    reference's EndPoint); ~2x loopback TCP on this box."""
    from incubator_brpc_tpu.utils.endpoint import EndPoint

    path = str(tmp_path / "native.sock")
    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(EchoService())
    assert srv.start(EndPoint.uds(path)) == 0
    assert srv._native_engine is not None
    try:
        pool = native.NativeClientPool(path, 0)
        req = EchoRequest(message="uds").SerializeToString()
        rc, body, att, ec, et, ct = pool.call(
            "EchoService", "Echo", req, timeout_ms=3000
        )
        assert rc == 0 and ec == 0
        from incubator_brpc_tpu.protos.echo_pb2 import EchoResponse

        resp = EchoResponse()
        resp.ParseFromString(body)
        assert resp.message == "uds"
        pool.destroy()
    finally:
        srv.stop()


def test_native_generic_method_dispatch(tmp_path):
    """The native dispatch is generic (engine.cpp NativeMethod): any
    registered handler — here a ctypes callback — answers on the C++
    frame cycle via the same registry as the built-in echo, and
    unregistered methods on the same service still fall back to the
    full Python stack."""
    from incubator_brpc_tpu.protos.echo_pb2 import EchoResponse
    from incubator_brpc_tpu.server.service import Service, ServiceStub, rpc_method

    import ctypes

    calls = []

    def reverse_handler(user_data, req, req_len, att, att_len, resp_ctx):
        # parse EchoRequest, answer with the reversed message
        data = ctypes.string_at(req, req_len)
        r = EchoRequest()
        r.ParseFromString(data)
        if r.sleep_us:  # decline: exercise handler-driven fallback
            return -1
        calls.append(r.message)
        out = EchoResponse(message=r.message[::-1]).SerializeToString()
        native.NativeServerEngine.resp_append_payload(resp_ctx, out)
        if att_len:
            native.NativeServerEngine.resp_append_attachment(
                resp_ctx, ctypes.string_at(att, att_len)
            )
        return 0

    class ReverseService(Service):
        SERVICE_NAME = "ReverseService"

        def native_fastpaths(self):
            return {"Echo": ("method", reverse_handler)}

        @rpc_method(EchoRequest, EchoResponse)
        def Echo(self, controller, request, response, done):
            # Python fallback (handler declines when sleep_us set)
            response.message = "py:" + request.message[::-1]
            done()

    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(ReverseService())
    assert srv.start(0) == 0
    assert srv._native_engine is not None
    try:
        ch = _channel(srv.port)
        stub = ServiceStub(ch, ReverseService)
        c = Controller()
        c.request_attachment.append(b"ATT")
        r = stub.Echo(c, EchoRequest(message="generic"))
        assert not c.failed(), c.error_text()
        assert r.message == "cireneg"
        assert c.response_attachment.to_bytes() == b"ATT"
        assert calls == ["generic"]
        # handler declines → Python handler answers
        c2 = Controller()
        r2 = stub.Echo(c2, EchoRequest(message="fall", sleep_us=1))
        assert not c2.failed(), c2.error_text()
        assert r2.message == "py:llaf"
        ch.close()
    finally:
        srv.stop()


def test_native_fastpath_overload_shed_and_stats_harvest():
    """ServerOptions.method_max_concurrency is enforced ON the fast
    path (C++ gate → EOVERCROWDED, the admission code mapping's
    "retry elsewhere" shed — server/admission.py), and fast-path
    completions fold into MethodStatus via harvest_native_stats so
    /status sees the traffic (round-3 advisor findings)."""
    import time as _t

    from incubator_brpc_tpu.protos.echo_pb2 import EchoResponse
    from incubator_brpc_tpu.server.service import Service, ServiceStub, rpc_method

    def slow_handler(user_data, req, req_len, att, att_len, resp_ctx):
        _t.sleep(0.4)  # releases the GIL: a second worker can reject in C++
        native.NativeServerEngine.resp_append_payload(
            resp_ctx, EchoResponse(message="slow").SerializeToString()
        )
        return 0

    class SlowService(Service):
        SERVICE_NAME = "SlowService"

        def native_fastpaths(self):
            return {"Echo": ("method", slow_handler)}

        @rpc_method(EchoRequest, EchoResponse)
        def Echo(self, controller, request, response, done):
            response.message = "py"
            done()

    srv = Server(
        ServerOptions(
            native_engine=True, method_max_concurrency=1, num_threads=2
        )
    )
    srv.add_service(SlowService())
    assert srv.start(0) == 0
    assert srv._native_engine is not None
    try:
        results = []

        def call(delay):
            _t.sleep(delay)
            ch = _channel(srv.port)  # own channel → own connection
            stub = ServiceStub(ch, SlowService)
            c = Controller()
            stub.Echo(c, EchoRequest(message="x"))
            results.append(c.error_code if c.failed() else 0)
            ch.close()

        ts = [
            threading.Thread(target=call, args=(d,)) for d in (0.0, 0.15)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sorted(results) == [0, errors.EOVERCROWDED], results
        # harvest: MethodStatus now carries the fast-path completion +
        # the rejection as an error
        srv.harvest_native_stats()
        status = srv.method_status("SlowService.Echo")
        assert status.latency_rec.count() == 1
        assert status.errors.get_value() == 1
        # avg latency reflects the 400ms handler
        assert status.latency_rec.latency() > 100_000
    finally:
        srv.stop()


def test_native_channel_over_uds(tmp_path):
    """connection_type=native over a UDS endpoint uses the C engine's
    UDS pool/mux instead of silently degrading (round-3 advisor low)."""
    from incubator_brpc_tpu.utils.endpoint import EndPoint

    path = str(tmp_path / "nch.sock")
    srv = Server(ServerOptions(native_engine=True))
    srv.add_service(EchoService())
    assert srv.start(EndPoint.uds(path)) == 0
    try:
        ch = Channel(ChannelOptions(connection_type="native", timeout_ms=5000))
        assert ch.init(f"unix:{path}") == 0
        assert ch.options.connection_type == "native"
        stub = echo_stub(ch)
        # sync path (multiplexed over the C mux reactor: nc_mux_call
        # parks the caller on a per-call waiter, no exclusive pooled fd)
        c = Controller()
        r = stub.Echo(c, EchoRequest(message="uds-native"))
        assert not c.failed(), c.error_text()
        assert r.message == "uds-native"
        assert ch._native_mux_obj is not None, "degraded off the C mux"
        # async (mux) path
        ev = threading.Event()
        c2 = Controller()
        r2 = stub.Echo(c2, EchoRequest(message="uds-async"), done=ev.set)
        assert ev.wait(5)
        assert not c2.failed(), c2.error_text()
        assert r2.message == "uds-async"
        assert ch._native_mux_obj is not None
        ch.close()
    finally:
        srv.stop()


def test_native_build_is_keyed_on_source_and_flags(tmp_path):
    """The engine loads only from ``_engine<suffix>-<key>.so``, key =
    hash of the source and the compile command: an edited source or
    changed flags name a different file, so a stale or copied-in build
    is never loaded."""
    import os
    import re

    from incubator_brpc_tpu import native

    name = os.path.basename(native._SO)
    assert re.fullmatch(
        rf"_engine{re.escape(native._SUFFIX)}-[0-9a-f]{{16}}\.so", name
    ), name
    src = tmp_path / "engine.cpp"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read())
    cmd = native._engine_cmd()
    keys = {native._keyed_so("_engine", str(src), cmd)}
    assert keys == {native._SO}
    keys.add(native._keyed_so("_engine", str(src), cmd + ["-DSTALE"]))
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    keys.add(native._keyed_so("_engine", str(src), cmd))
    assert len(keys) == 3
