"""The ICI data plane: the same-chip transmit chosen by size (the
whole-frame kernel or the fused chunk program), chunk-accumulating
checksums, coalesced delivery, and the credit-flow invariants under
partial pipeline failure (docs/ici_pipeline.md).

Runs on whatever backend the environment offers; checksum-equality
tests force Pallas interpret mode so the REAL kernels' semantics are
exercised off-TPU (pallas_guide: interpret mode).
"""

import threading
import time as _time

import pytest

from incubator_brpc_tpu import errors
from incubator_brpc_tpu.utils.iobuf import IOBuf
from incubator_brpc_tpu.utils.segmentation import (
    chunk_views,
    plan_chunks,
    plan_row_chunks,
)

_coords_counter = [300]


def fresh_coords():
    _coords_counter[0] += 1
    return (9, _coords_counter[0])


# ---- chunk planner ---------------------------------------------------------


def test_plan_chunks_one_byte_tail():
    chunks = plan_chunks(4 * 1024 + 1, chunk_bytes=1024)
    assert chunks == [(0, 1024), (1024, 1024), (2048, 1024),
                      (3072, 1024), (4096, 1)]
    assert plan_chunks(0, 1024) == []
    with pytest.raises(ValueError):
        plan_chunks(10, 0)


def test_chunk_views_one_byte_tail_reassembles():
    payload = bytes(range(256)) * 17  # 4352 = 4 * 1024 + 256
    views = [memoryview(payload[:4096]), memoryview(payload[4096:4351]),
             memoryview(payload[4351:])]  # last view is ONE byte
    out = b"".join(
        bytes(c) for c in chunk_views(views, 1024)
    )
    assert out == payload


def test_plan_row_chunks_alignment():
    # chunk boundaries stay multiples of align_rows; tail may be short
    chunks = plan_row_chunks(320, row_bytes=1024, chunk_bytes=128 * 1024,
                             align_rows=64)
    assert chunks == [(0, 128), (128, 128), (256, 64)]
    assert all(off % 64 == 0 for off, _ in chunks)
    # chunk_bytes below one aligned row-group clamps UP to align_rows
    chunks = plan_row_chunks(256, row_bytes=1024, chunk_bytes=1024,
                             align_rows=64)
    assert chunks[0][1] == 64
    with pytest.raises(ValueError):
        plan_row_chunks(100, 1024, 1 << 20, align_rows=64)


# ---- chunk-accumulating checksum (interpret mode = real kernels) -----------


@pytest.mark.parametrize(
    "m,n,chunk_bytes",
    [
        (512, 256, 128 * 256 * 4),   # exact chunk multiples
        (320, 256, 100 * 256 * 4),   # m not a chunk multiple (short tail)
        (1000, 128, 4096 * 128),     # odd m: block rows fall to 8
        (1, 128, 64),                # single-row frame, one chunk
    ],
)
def test_chunked_checksum_equals_whole_frame_interpret(m, n, chunk_bytes):
    """Chunked and whole-frame copy+checksum must agree BIT-FOR-BIT:
    the chained accumulator performs the same f32 additions in the same
    order (the property the receiver's one-value-per-frame verification
    rests on)."""
    import numpy as np
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops.transfer import (
        device_copy_with_checksum,
        device_copy_with_checksum_chunked,
    )

    x = jnp.asarray(np.random.RandomState(m).randn(m, n).astype(np.float32))
    whole_out, whole_csum = device_copy_with_checksum(x, interpret=True)
    chunk_out, chunk_csum = device_copy_with_checksum_chunked(
        x, chunk_bytes=chunk_bytes, interpret=True
    )
    assert chunk_out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(whole_out), np.asarray(chunk_out))
    assert float(whole_csum) == float(chunk_csum)


# ---- chunked transmit through a real RPC -----------------------------------


@pytest.fixture
def chunked_fabric(monkeypatch):
    from incubator_brpc_tpu.parallel.ici import get_fabric

    fabric = get_fabric()
    # small: a 1MB payload chunks even here
    monkeypatch.setattr(fabric, "chunk_bytes", 64 * 1024)
    yield fabric


def _ici_echo_server():
    import jax

    from incubator_brpc_tpu.models.echo import EchoService
    from incubator_brpc_tpu.server.server import Server

    srv = Server()
    srv.add_service(EchoService())
    s, c = fresh_coords()
    assert srv.start_ici(s, c, device=jax.devices()[0]) == 0
    return srv, f"ici://slice{s}/chip{c}"


def test_fused_chunked_echo_content(chunked_fabric):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.models.echo import echo_stub
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    srv, addr = _ici_echo_server()
    try:
        ch = Channel(
            ChannelOptions(timeout_ms=30000, ici_device=jax.devices()[0])
        )
        assert ch.init(addr) == 0
        stub = echo_stub(ch)
        x = jnp.arange(512 * 512, dtype=jnp.float32).reshape(512, 512)
        c = Controller()
        c.request_attachment.append_device(x)
        stub.Echo(c, EchoRequest(message="bulk"))
        assert not c.failed(), c.error_text()
        arrs = c.response_attachment.device_arrays()
        assert len(arrs) == 1 and arrs[0].shape == (512, 512)
        assert arrs[0] is not x, "chunked transmit must produce a fresh buffer"
        np.testing.assert_array_equal(np.asarray(arrs[0]), np.asarray(x))
    finally:
        srv.stop()


# ---- one transmit path, chosen by size --------------------------------------


@pytest.mark.parametrize(
    "shape,dtype,program,chunks",
    [
        # 4 KiB: the whole-frame kernel
        ((8, 128), "float32", "whole", 0),
        # 8 rows under MIN_CHUNKS x 64 KiB: still the whole-frame kernel
        ((248, 128), "float32", "whole", 0),
        # exactly at the line: fused — one chunk, since chunks align to
        # the frame's one 256-row block
        ((256, 128), "float32", "fused", 1),
        # 4 chunks of 128 rows plus an 8-row tail
        ((520, 128), "float32", "fused", 5),
        # 1-D bytes, lane-viewed as (256, 4096)
        ((1 << 20,), "uint8", "fused", 2),
        ((512, 256), "bfloat16", "fused", 2),
        # no kernel compiles for float16: the XLA copy, unchecked
        ((512, 256), "float16", "xla", 0),
        # 40,000 f32 is not a multiple of 128 lanes: nothing tiles
        ((40_000,), "float32", "xla", 0),
    ],
)
def test_transmit_program_is_chosen_by_size(
    chunked_fabric, monkeypatch, shape, dtype, program, chunks
):
    """A segment sent through the fabric takes exactly one program,
    picked by its size (and, below that, by whether a kernel can take
    it): the output is bit-equal and a fresh buffer, and a kernel's
    checksum equals the whole-frame kernel's on the same data."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.ops import transfer as T
    from incubator_brpc_tpu.parallel.ici import ici_unchecked_segments

    whole, fused, xla = (
        T.device_copy_with_checksum, T._chunked_copy_csum, T._xla_copy
    )
    calls = []

    def count_whole(x, interpret=False):
        calls.append(("whole", 0))
        return whole(x, interpret=True)

    def count_fused(x, chunks, block_rows, interpret):
        calls.append(("fused", len(chunks)))
        return fused(x, chunks=chunks, block_rows=block_rows, interpret=True)

    def count_xla(x):
        calls.append(("xla", 0))
        return xla(x)

    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)
    monkeypatch.setattr(T, "device_copy_with_checksum", count_whole)
    monkeypatch.setattr(T, "_chunked_copy_csum", count_fused)
    monkeypatch.setattr(T, "_xla_copy", count_xla)

    rs = np.random.RandomState(len(shape) + shape[0])
    if dtype == "uint8":
        x = jnp.asarray(rs.randint(0, 256, shape).astype(np.uint8))
    else:
        x = jnp.asarray(rs.randn(*shape).astype(np.float32), dtype)
    fabric = chunked_fabric
    _, coords, drained, _ = _stub_port(fabric, device=jax.devices()[0])
    unchecked0 = int(ici_unchecked_segments.get_value())
    try:
        frame = IOBuf()
        frame.append_device(x)
        assert fabric.send(frame, coords, fresh_coords()) == 0
        assert _wait_for(lambda: len(drained) == 1)
        assert drained[0] is frame
    finally:
        fabric.unregister(coords)
    (ref,) = frame.device_segments()
    assert calls == [(program, chunks)], calls
    assert ref.array is not x, "transmit must produce a fresh buffer"
    assert ref.array.shape == x.shape and ref.array.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(ref.array), np.asarray(x))
    unchecked = int(ici_unchecked_segments.get_value()) - unchecked0
    if program == "xla":
        assert ref.csum is None and unchecked == 1
    else:
        want = whole(T.lanes_view(x), interpret=True)[1]
        assert float(ref.csum) == float(want) and unchecked == 0


@pytest.mark.parametrize(
    "shape,walked",
    [
        ((248, 128), None),  # under the line: no chunks, no walk
        ((520, 128), 5),     # the fused program's 5 planned chunks
        ((40_000,), 0),      # at the line but untileable: an empty plan
    ],
)
def test_chunk_walk_follows_the_fused_plan(shape, walked):
    """The ici.chunk walk runs only for frames at or above the size
    line, over the plan the fused program would run — so a FaultPlan's
    traversal indices are the chunks that dispatch."""
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.ops import transfer as T

    x = jnp.ones(shape, jnp.float32)
    seen = []
    out, _ = T.transmit_array_chunked(x, 64 << 10, walk=seen.append)
    assert seen == ([] if walked is None else [walked]), seen
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ---- partial pipeline failure: credits must not leak (satellite) -----------


@pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
def test_chunk_fault_releases_window_and_surfaces_one_error(
    chunked_fabric, warm
):
    """Seeded FaultPlan fires an ici.chunk reset mid-frame — the fused
    program walks its chunk plan through the site before dispatch, on
    a warm connection or on its very first frame: the sender gets ONE
    ERPC error (EINTERNAL — the fabric connection stays up), the site
    fired once, the receive window shows zero queued bytes afterwards,
    and the very next call on the same socket succeeds."""
    import jax
    import jax.numpy as jnp

    from incubator_brpc_tpu.chaos import FaultPlan
    from incubator_brpc_tpu.chaos import injector as chaos_injector
    from incubator_brpc_tpu.chaos.plan import FaultSpec
    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.models.echo import echo_stub
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    srv, addr = _ici_echo_server()
    try:
        ch = Channel(
            ChannelOptions(timeout_ms=30000, ici_device=jax.devices()[0])
        )
        assert ch.init(addr) == 0
        stub = echo_stub(ch)
        x = jnp.ones((1024, 256), jnp.float32)  # 1MB → 16 chunks of 64KB
        if warm:
            c0 = Controller()
            c0.request_attachment.append_device(x)
            stub.Echo(c0, EchoRequest(message="warm"))
            assert not c0.failed(), c0.error_text()

        plan = FaultPlan(
            [FaultSpec("ici.chunk", "reset", probability=1.0, max_hits=1)],
            seed=1234 if warm else 77,
            name="chunk-fault",
        )
        chaos_injector.arm(plan)
        try:
            c = Controller()
            c.max_retry = 0
            c.request_attachment.append_device(x)
            stub.Echo(c, EchoRequest(message="bulk"))
            assert c.failed()
            assert c.error_code == errors.EINTERNAL, (
                c.error_code, c.error_text(),
            )
            hits = chaos_injector.site_hits().get("ici.chunk", {})
            assert sum(hits.values()) == 1, hits
        finally:
            chaos_injector.disarm()
        # the faulted frame reserved no window credit — nothing leaks
        assert srv._ici_port._queued_bytes == 0
        # and the fabric connection survived: same socket, next call ok
        c2 = Controller()
        c2.request_attachment.append_device(x)
        stub.Echo(c2, EchoRequest(message="after"))
        assert not c2.failed(), c2.error_text()
    finally:
        srv.stop()


# ---- coalesced delivery: send_batch / delivery_burst / execute_batch -------


def _stub_port(fabric, window_bytes=None, device=None):
    """Server port whose completion queue records drained frames and
    releases window credits like _drain_completions does."""
    coords = fresh_coords()
    port = fabric.register(coords, server=object(), device=device)
    drained = []
    calls = []

    def consumer(batch):
        calls.append(len(batch))
        for (frame, src, _sender), _accepted_us in batch:
            drained.append(frame if device is not None
                           else bytes(frame.to_bytes()))
            with port._qb_lock:
                port._queued_bytes -= len(frame)

    port._cq._consumer = consumer
    if window_bytes is not None:
        port.overcrowded_bytes = window_bytes
    return port, coords, drained, calls


def _wait_for(pred, timeout=5.0):
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        if pred():
            return True
        _time.sleep(0.01)
    return pred()


def test_send_batch_single_wake_in_order():
    from incubator_brpc_tpu.parallel.ici import get_fabric

    fabric = get_fabric()
    port, coords, drained, calls = _stub_port(fabric)
    try:
        frames = [IOBuf(bytes([65 + i]) * (i + 1)) for i in range(5)]
        rcs = fabric.send_batch(frames, coords, fresh_coords())
        assert rcs == [0] * 5
        assert _wait_for(lambda: len(drained) == 5)
        assert drained == [bytes([65 + i]) * (i + 1) for i in range(5)]
        # ONE consumer wake drained the whole burst
        assert calls == [5], calls
        assert port._queued_bytes == 0
    finally:
        fabric.unregister(coords)


def test_send_batch_window_overflow_fails_frames_individually():
    from incubator_brpc_tpu.parallel.ici import get_fabric

    fabric = get_fabric()
    port, coords, drained, calls = _stub_port(fabric, window_bytes=300)
    try:
        frames = [IOBuf(b"x" * 120) for _ in range(4)]
        rcs = fabric.send_batch(frames, coords, fresh_coords())
        # first two fit the 300B window; the rest bounce at admission
        assert rcs[:2] == [0, 0]
        assert all(rc == errors.EOVERCROWDED for rc in rcs[2:]), rcs
        assert _wait_for(lambda: len(drained) == 2)
        assert port._queued_bytes == 0  # admitted credits fully returned
    finally:
        fabric.unregister(coords)


def test_delivery_burst_defers_consumer_wake():
    from incubator_brpc_tpu.parallel.ici import get_fabric

    fabric = get_fabric()
    port, coords, drained, calls = _stub_port(fabric)
    try:
        src = fresh_coords()
        with fabric.delivery_burst():
            assert fabric.send(IOBuf(b"one"), coords, src) == 0
            assert fabric.send(IOBuf(b"two"), coords, src) == 0
            # window credits reserved immediately...
            assert port._queued_bytes == 6
            # ...but no consumer ran yet: frames wait for the flush
            _time.sleep(0.05)
            assert drained == []
        assert _wait_for(lambda: len(drained) == 2)
        assert drained == [b"one", b"two"]
        assert calls == [2]
        assert port._queued_bytes == 0
    finally:
        fabric.unregister(coords)


def test_delivery_burst_bulk_frame_bypasses_capture():
    """Frames ≥ BURST_BYPASS_BYTES dispatch immediately inside a burst:
    coalescing amortizes microsecond-scale wakes for small RPCs, and
    must not hold a bulk frame's receive work hostage to burst close."""
    from incubator_brpc_tpu.parallel.ici import (
        BURST_BYPASS_BYTES,
        get_fabric,
    )

    fabric = get_fabric()
    port, coords, drained, calls = _stub_port(fabric)
    try:
        src = fresh_coords()
        with fabric.delivery_burst():
            assert fabric.send(IOBuf(b"small"), coords, src) == 0
            bulk = IOBuf(b"\xa5" * BURST_BYPASS_BYTES)
            assert fabric.send(bulk, coords, src) == 0
            # the bulk frame dispatched without waiting for burst close…
            assert _wait_for(lambda: len(drained) == 1)
            assert len(drained[0]) == BURST_BYPASS_BYTES
            # …while the small frame stays captured until the flush
            assert b"small" not in drained
        assert _wait_for(lambda: len(drained) == 2)
        assert drained[1] == b"small"
        assert port._queued_bytes == 0
    finally:
        fabric.unregister(coords)


def test_execute_batch_refused_after_stop_and_credits_released():
    from incubator_brpc_tpu.parallel.ici import get_fabric
    from incubator_brpc_tpu.runtime.execution_queue import ExecutionQueue

    q = ExecutionQueue(lambda batch: None)
    q.stop()
    assert q.execute_batch([1, 2, 3]) is False
    assert q.execute_batch([]) is True  # empty batch is a no-op

    # a port whose queue stopped must refuse delivery AND give the
    # window credits back (the leak the close/send race would cause)
    fabric = get_fabric()
    coords = fresh_coords()
    port = fabric.register(coords, server=object())
    try:
        port._cq.stop()
        port._cq.join(2)
        assert port.deliver(IOBuf(b"x" * 64), fresh_coords()) is False
        assert port._queued_bytes == 0
        # a burst flush hitting a stopped queue must return the credits
        # its deliveries reserved
        with port._qb_lock:
            port._queued_bytes += 32
        port._flush_burst([(IOBuf(b"y" * 32), fresh_coords())])
        assert port._queued_bytes == 0
    finally:
        fabric.unregister(coords)


def test_close_racing_send_reports_connection_failure_not_backpressure(
    monkeypatch,
):
    """A port that closes between the fabric's lookup and delivery must
    surface EFAILEDSOCKET (dead destination), not EOVERCROWDED —
    retry/circuit-breaker accounting keys on the difference, and no
    window credit may stick to the refused frame."""
    from incubator_brpc_tpu.parallel.ici import get_fabric

    fabric = get_fabric()
    port, coords, _, _ = _stub_port(fabric)
    try:
        port.closed = True  # close "wins" the race...
        port._cq.stop()
        # ...but the sender already resolved the port object
        monkeypatch.setattr(
            fabric, "port", lambda c: port if c == coords else None
        )
        rc = fabric.send(IOBuf(b"x" * 64), coords, fresh_coords())
        assert rc == errors.EFAILEDSOCKET, rc
        assert port._queued_bytes == 0
    finally:
        monkeypatch.undo()
        fabric.unregister(coords)


def test_execution_queue_execute_batch_orders_and_drains():
    from incubator_brpc_tpu.runtime.execution_queue import ExecutionQueue

    seen = []
    done = threading.Event()

    def consume(batch):
        seen.extend(batch)
        if len(seen) >= 10:
            done.set()

    q = ExecutionQueue(consume)
    assert q.execute_batch(range(10)) is True
    assert done.wait(5)
    assert seen == list(range(10))


def test_pallas_one_byte_wire_tail_survives_pallas_mode(chunked_fabric):
    """A host-bytes attachment whose size leaves a ONE-byte wire tail
    must reassemble byte-exact while the fabric chunks device frames at
    64 KiB — the device transmit must not disturb the byte-plane
    chunker."""
    import jax

    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.models.echo import echo_stub
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    srv, addr = _ici_echo_server()
    try:
        ch = Channel(
            ChannelOptions(timeout_ms=30000, ici_device=jax.devices()[0])
        )
        assert ch.init(addr) == 0
        stub = echo_stub(ch)
        # 4 full 64KB wire chunks + a one-byte tail
        payload = bytes(range(256)) * 1024 + b"\x7f"
        assert len(payload) == 4 * chunked_fabric.chunk_bytes + 1
        c = Controller()
        c.request_attachment.append(payload)
        stub.Echo(c, EchoRequest(message="tail"))
        assert not c.failed(), c.error_text()
        assert c.response_attachment.to_bytes() == payload
    finally:
        srv.stop()


# ---- PR 21: what the TPU compiler refused (tests/test_chip_compile.py
# compiles these for v5e; here the interpreter pins their semantics) ----


@pytest.mark.parametrize(
    "dtype", ["float32", "uint8", "uint16", "uint32", "int8", "bfloat16"]
)
def test_every_lane_agrees_per_kernel_dtype_interpret(dtype):
    """The whole-frame kernel and the fused chunk program copy exactly
    and give ONE checksum for every kernel dtype — unsigned
    payloads (the cache's uint8 values) widen through int32, since
    Mosaic has no unsigned->f32 cast (uint32 wraps, deterministically)."""
    import numpy as np
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    rs = np.random.RandomState(7)
    if dtype in ("float32", "bfloat16"):
        x = jnp.asarray(rs.randn(512, 256).astype(np.float32), dtype)
    else:
        info = np.iinfo(dtype)
        x = jnp.asarray(
            rs.randint(info.min, int(info.max) + 1, (512, 256), np.int64)
            .astype(dtype)
        )
    assert x.dtype in T.KERNEL_DTYPES
    _, br, chunks = T.chunk_plan_for(x, 64 << 10)
    assert len(chunks) >= 2, (br, chunks)
    runs = {
        "whole": T.device_copy_with_checksum(x, interpret=True),
        "fused": T.device_copy_with_checksum_chunked(
            x, chunk_bytes=64 << 10, interpret=True
        ),
    }
    for name, (out, _) in runs.items():
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x), name)
    sums = {name: float(c) for name, (_, c) in runs.items()}
    assert len(set(sums.values())) == 1, sums
    xn = np.asarray(x)
    if xn.dtype.kind == "u":
        xn = xn.astype(np.int32)  # the kernels' widening rule
    ref = float(np.sum(xn.astype(np.float64)))
    assert abs(sums["whole"] - ref) <= 1e-5 * max(1.0, abs(ref)), (sums, ref)


def test_block_rows_are_sized_by_bytes():
    """A fixed 256 rows overflowed v5e's VMEM on wide rows; the block is
    now bounded by bytes too, and a block that breaks the dtype's
    sublane tiling means the view does not tile."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops.transfer import _fit_block_rows, lanes_view

    f32, bf16, u8 = jnp.float32, jnp.bfloat16, jnp.uint8
    assert _fit_block_rows(8192, 2048, f32) == 256  # bench plan unchanged
    assert _fit_block_rows(4096, 4096, f32) == 128
    assert _fit_block_rows(1024, 8192, bf16) == 64
    assert _fit_block_rows(1000, 128, f32) == 8
    assert _fit_block_rows(1000, 128, bf16) == 0  # 8 rows < bf16's 16
    assert _fit_block_rows(3, 4096, u8) == 3      # a whole view always tiles
    # 1D values pick the first lane count whose rows tile
    assert lanes_view(jnp.zeros(1 << 20, u8)).shape == (256, 4096)
    assert lanes_view(jnp.zeros(4096 * 1000, u8)).shape == (4000, 1024)
    assert lanes_view(jnp.zeros(1000, u8)) is None


# ---- the drain-batch transmit program (transfer.transmit_batch) -------------


@pytest.fixture
def batch_lane(monkeypatch):
    """The kernel lane here: arrays count as on the TPU, and the
    whole-frame kernel and the batch program run through the Pallas
    interpreter.  Yields the bucket of every batch program run."""
    from incubator_brpc_tpu.ops import transfer as T

    whole, batch = T.device_copy_with_checksum, T._batch_copy_csum
    buckets = []

    def run_batch(xs, interpret=False):
        buckets.append(len(xs))
        return batch(xs, interpret=True)

    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)
    monkeypatch.setattr(
        T, "device_copy_with_checksum",
        lambda x, interpret=False: whole(x, interpret=True),
    )
    monkeypatch.setattr(T, "_batch_copy_csum", run_batch)
    yield buckets


def _segments(spec):
    """Arrays from ``[(count, shape, dtype), ...]``, each distinct."""
    import jax.numpy as jnp
    import numpy as np

    out = []
    for count, shape, dtype in spec:
        for _ in range(count):
            rs = np.random.RandomState(len(out) + 1)
            if dtype == "uint8":
                out.append(jnp.asarray(rs.randint(0, 256, shape), jnp.uint8))
            else:
                out.append(jnp.asarray(rs.randn(*shape), dtype))
    return out


@pytest.mark.parametrize(
    "spec,sizes,buckets",
    [
        ([(2, (8, 128), "float32")], [2], [2]),
        # 3 in a bucket of 4: one padded slot
        ([(3, (8, 128), "float32")], [3], [4]),
        ([(4, (8, 128), "float32")], [4], [4]),
        # 5 in a bucket of 8: three padded slots
        ([(5, (8, 128), "float32")], [5], [8]),
        ([(8, (8, 128), "float32")], [8], [8]),
        # beyond the largest bucket: cut into programs of 8; a lone
        # leftover takes the whole-frame kernel
        ([(11, (8, 128), "float32")], [8, 3], [8, 4]),
        ([(9, (8, 128), "float32")], [8], [8]),
        # mixed shapes and dtypes: one program a shape; uint8 1-D is
        # lane-viewed, and the lone bf16 goes alone
        ([(3, (8, 128), "float32"), (2, (4096,), "uint8"),
          (1, (4, 8, 128), "bfloat16")], [3, 2], [4, 2]),
    ],
    ids=["2", "3_padded", "4", "5_padded", "8", "11_cut", "9_lone",
         "mixed"],
)
def test_batch_program_equals_whole_frame_kernel_interpret(
    batch_lane, spec, sizes, buckets
):
    """Every segment of a drain batch comes back as a fresh, bit-equal
    copy of its own shape, with the whole-frame kernel's checksum on
    that segment bit for bit, for every bucket, padding included."""
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.ops import transfer as T

    xs = _segments(spec)
    # interleave the shapes, as replies of a drain batch arrive
    xs = xs[::2] + xs[1::2]
    assert all(T.batchable(x, 8 << 20) for x in xs)
    T.transmit_batch(xs)  # the first batch of a shape warms its buckets
    batch_lane.clear()
    results, got_sizes = T.transmit_batch(xs)
    assert got_sizes == sizes
    assert batch_lane == buckets, batch_lane
    assert len(results) == len(xs)
    for x, (out, csum) in zip(xs, results):
        assert out is not x
        assert out.shape == x.shape and out.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
        want = T.device_copy_with_checksum(T.lanes_view(x))[1]
        assert float(csum) == float(want)
    stacked = [c.stacked for _, c in results
               if isinstance(c, T.StackedChecksum)]
    # one stacked checksum output a program, sized by its bucket
    assert sorted({s.shape[0] for s in stacked}) == sorted(set(buckets))
    assert all(s.dtype == jnp.float32 for s in stacked)


def test_batch_warm_up_compiles_every_bucket_once(batch_lane):
    """The first batch of a shape runs every bucket's program (and the
    whole-frame kernel a lone segment takes); a later batch of that
    shape runs only its own program."""
    from incubator_brpc_tpu.ops import transfer as T

    T._batch_warm.clear()
    T.transmit_batch(_segments([(3, (16, 128), "float32")]))
    assert batch_lane == [*T.BATCH_BUCKETS, 4]
    T.transmit_batch(_segments([(2, (16, 128), "float32")]))
    assert batch_lane == [*T.BATCH_BUCKETS, 4, 2]


@pytest.mark.parametrize(
    "shape,dtype,nbytes_line",
    [
        ((512, 256), "float16", 8 << 20),   # no kernel compiles for it
        ((40_000,), "float32", 8 << 20),    # not a multiple of 128 lanes
        ((256, 128), "float32", 64 << 10),  # at the size line: fused
    ],
    ids=["float16", "untileable", "at_the_line"],
)
def test_what_the_whole_frame_kernel_does_not_take_is_not_batched(
    monkeypatch, shape, dtype, nbytes_line
):
    """A float16 or untileable segment keeps the XLA-copy lane, and one
    at the size line keeps the fused chunk program: none is batchable."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)
    x = jnp.ones(shape, dtype)
    assert not T.batchable(x, nbytes_line)
    if dtype == "float16" or shape == (40_000,):
        out, csum = T.transmit_array(x)
        assert csum is None and bool(jnp.array_equal(out, x))
    monkeypatch.setattr(T, "_on_tpu", lambda arr: False)
    assert not T.batchable(jnp.ones((8, 128), jnp.float32), nbytes_line)
