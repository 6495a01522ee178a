"""rpcz capture: every span of a profiler window, kept whole.

While a JAX profiler session records (or between ``start_capture`` and
``stop_capture``) every span is created, unsampled, and kept in the
capture buffer: an in-process ICI echo (inline and queued dispatch) and
a redis GET/SET over ``ici://`` each leave one client span, one server
span and two fabric legs per call, all on the caller's trace id, with
every boundary stamp set and in order.  With no session and rpcz off
nothing is created.  Span stamps and the profiler's events share one
clock once ``profile_start_time`` is added.
"""

import glob
import tempfile

import jax
import jax.numpy as jnp
import pytest

from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.models.echo import EchoService, echo_stub
from incubator_brpc_tpu.observability import span as rpcz
from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest
from incubator_brpc_tpu.server.server import Server, ServerOptions
from incubator_brpc_tpu.utils.flags import set_flag

CALLS = 4
SLICE = 37  # fabric coords clear of other tests' ports


@pytest.fixture
def rpcz_off():
    set_flag("rpcz_enabled", False)
    yield
    set_flag("rpcz_enabled", True)


def _echo_rig(chip, inline):
    dev = jax.devices()[0]
    srv = Server(ServerOptions(usercode_in_dispatcher=inline))
    srv.add_service(EchoService())
    assert srv.start_ici(SLICE, chip, device=dev) == 0
    ch = Channel(ChannelOptions(ici_device=dev, timeout_ms=20000))
    assert ch.init(f"ici://slice{SLICE}/chip{chip}") == 0
    stub = echo_stub(ch)
    payload = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)

    def call(k):
        c = Controller()
        c.request_attachment.append_device(payload + k)
        stub.Echo(c, EchoRequest(message=f"m{k}"))
        assert not c.failed(), c.error_text()
        return c

    return srv, ch, call


def _redis_rig(chip):
    import incubator_brpc_tpu.protocols.redis as R
    from incubator_brpc_tpu.cache import HBMCacheService

    dev = jax.devices()[0]
    srv = Server(ServerOptions(redis_service=HBMCacheService(device=dev)))
    assert srv.start_ici(SLICE, chip, device=dev) == 0
    ch = Channel(ChannelOptions(protocol="redis", ici_device=dev,
                                timeout_ms=20000))
    assert ch.init(f"ici://slice{SLICE}/chip{chip}") == 0
    spec = R.redis_method_spec()

    def call(k):
        req = R.RedisRequest()
        if k % 2 == 0:
            req.add_command("SET", b"key%d" % k, b"v" * 100)
        else:
            req.add_command("GET", b"key%d" % (k - 1))
        resp = R.RedisResponse()
        c = Controller()
        ch.call_method(spec, c, req, resp)
        assert not c.failed(), c.error_text()
        assert not resp.reply(0).is_error()
        return c

    return srv, ch, call


def _ordered(span, fields):
    vals = [getattr(span, f, 0) for f in fields]
    assert all(vals), (span.describe(), fields, vals)
    assert vals == sorted(vals), (span.describe(), fields, vals)


@pytest.mark.parametrize("rig", ["echo_inline", "echo_queued", "redis"])
def test_profiler_session_captures_every_call(tmp_path, rpcz_off, rig):
    chip = {"echo_inline": 1, "echo_queued": 2, "redis": 3}[rig]
    if rig == "redis":
        srv, ch, call = _redis_rig(chip)
    else:
        srv, ch, call = _echo_rig(chip, inline=rig == "echo_inline")
    try:
        call(-2)  # warm: compiles every shape outside the window
        jax.profiler.start_trace(str(tmp_path))
        try:
            ctrls = [call(k) for k in range(CALLS)]
        finally:
            jax.profiler.stop_trace()
    finally:
        ch.close()
        srv.stop()
    cap = rpcz.last_capture()
    assert cap is not None and cap.overflow == 0
    assert 0 < cap.start_us < cap.stop_us
    traces = [c._span.trace_id for c in ctrls]
    assert len(set(traces)) == CALLS, "one trace per call"
    for c, tid in zip(ctrls, traces):
        spans = [s for s in cap.spans if s.trace_id == tid]
        by_kind = {}
        for s in spans:
            by_kind.setdefault(s.kind, []).append(s)
        assert sorted(by_kind) == ["client", "collective", "server"], spans
        (client,) = by_kind["client"]
        (server,) = by_kind["server"]
        legs = by_kind["collective"]
        assert len(legs) == 2 and all(s.service == "ici" for s in legs)
        assert client is c._span
        assert cap.start_us <= client.start_us <= client.end_us <= cap.stop_us
        # the server and the request leg hang off the client span; the
        # reply leg off the server span
        assert server.parent_span_id == client.span_id
        assert sorted(s.parent_span_id for s in legs) == sorted(
            [client.span_id, server.span_id])
        _ordered(client, ["start_us", "response_write_us", "received_us",
                          "dequeued_us", "end_us"])
        _ordered(server, ["received_us", "dequeued_us", "parse_done_us",
                          "callback_start_us", "callback_done_us",
                          "response_write_us", "sent_us", "end_us"])
        for leg in legs:
            _ordered(leg, ["start_us", "placed_us", "end_us"])
        phases = dict(server.phase_deltas())
        assert "cq_wait" in phases and "parse" in phases
        assert "place" in dict(legs[0].phase_deltas())


def test_nothing_is_created_with_no_session_and_rpcz_off(rpcz_off,
                                                          monkeypatch):
    made = []
    init = rpcz.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(a)
        init(self, *a, **kw)

    srv, ch, call = _echo_rig(4, inline=True)
    try:
        call(-1)
        before = rpcz.last_capture()
        monkeypatch.setattr(rpcz.Span, "__init__", counting_init)
        ctrls = [call(k) for k in range(CALLS)]
    finally:
        ch.close()
        srv.stop()
    assert made == []
    assert all(c._span is None for c in ctrls)
    after = rpcz.last_capture()
    if before is None:
        assert after is None
    else:
        assert (after.start_us, len(after.spans)) == (
            before.start_us, len(before.spans))


def test_sampled_rpcz_leaves_the_redis_server_span_out(monkeypatch):
    """Without a capture, a sampled redis call over the fabric gets its
    client span and request leg only: the server span and the reply's
    leg would be Collector work on the serving path."""
    made = []
    create = rpcz.Span.create_server.__func__

    def counting(cls, service, *a):
        made.append(service)
        return create(cls, service, *a)

    srv, ch, call = _redis_rig(5)
    set_flag("rpcz_max_spans_per_second", 1_000_000)  # every call sampled
    try:
        call(0)
        monkeypatch.setattr(rpcz.Span, "create_server", classmethod(counting))
        ctrls = [call(k) for k in range(CALLS)]
    finally:
        set_flag("rpcz_max_spans_per_second", 500)
        ch.close()
        srv.stop()
    assert "redis" not in made
    assert all(c._span is not None for c in ctrls)  # sampled, not captured


def test_explicit_capture_interval_and_overflow(rpcz_off, monkeypatch):
    monkeypatch.setattr(rpcz, "CAPTURE_MAX_SPANS", 3)
    cap = rpcz.start_capture()
    try:
        spans = [rpcz.Span.create_client("cap", f"m{i}") for i in range(5)]
    finally:
        rpcz.stop_capture()
    late = rpcz.Span.create_client("cap", "after")
    assert late is None  # disarmed, rpcz off: nothing created
    for s in spans:
        s.end()
    got = rpcz.last_capture()
    assert got.start_us == cap.start_us and got.stop_us >= got.start_us
    assert [s.method for s in got.spans] == ["m0", "m1", "m2"]
    assert got.overflow == 2  # counted, never silent
    assert rpcz.span_db().by_trace(spans[0].trace_id) == []  # not rpcz's


def test_capture_skips_the_sampling_budget():
    """Armed, every span is created even past the creation budget."""
    set_flag("rpcz_max_spans_per_second", 1)
    try:
        rpcz.start_capture()
        try:
            spans = [rpcz.Span.create_client("cap", "burst")
                     for _ in range(50)]
        finally:
            rpcz.stop_capture()
    finally:
        set_flag("rpcz_max_spans_per_second", 500)
    assert all(s is not None for s in spans)


def test_span_and_trace_annotation_share_one_clock(rpcz_off):
    d = tempfile.mkdtemp(prefix="rpcz-clock-")
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation("rpcz.clock"):
            span = rpcz.Span.create_client("clock", "probe")
    finally:
        jax.profiler.stop_trace()
    assert span is not None  # created by the session, rpcz off
    span.end()
    from jax.profiler import ProfileData

    from incubator_brpc_tpu.observability.profiling import profile_start_ns

    (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    base = profile_start_ns(pd)
    starts = [e.start_ns for p in pd.planes for ln in p.lines
              for e in ln.events if e.name == "rpcz.clock"]
    assert base and len(starts) == 1
    assert abs(base + starts[0] - span.start_us * 1000) <= 200_000
