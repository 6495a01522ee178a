"""ICI fabric transport — the RDMA-endpoint analog for TPU.

Reference template: the RDMA subsystem (rdma/rdma_endpoint.h:63-227):
an alternative data path under the same Socket abstraction, with
pre-registered memory (block_pool), zerocopy send/recv straight from
IOBuf blocks, and completion polling wired into the same event
machinery. Here (north star): frames are IOBufs whose DeviceRef
segments are HBM-resident jax.Arrays; "transmission" runs the payload
through the fused Pallas copy+checksum kernel (same chip — one real
HBM traversal per hop, receiver gets a fresh buffer + integrity
checksum) or issues an XLA device-to-device transfer (cross chip) —
host bytes only ever materialize for the small meta header. Set
``IciFabric.zero_copy`` for the explicit reference-move fast path. Completion delivery uses an ExecutionQueue per port — the
"libtpu completion queue polled instead of epoll" — feeding the exact
same protocol parse path as TCP (one framing, two transports).

Single-process scope in round 1: the fabric routes between ici://
coordinates registered in this process (the test harness's in-process
multi-node pattern, SURVEY.md §4); the cross-host hop (DCN bootstrap,
like RDMA's TCP side-channel handshake) plugs in behind
``IciFabric.send`` later without touching callers.
"""

from __future__ import annotations

import contextlib
import threading
import time as _time
import weakref
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from incubator_brpc_tpu import errors
from incubator_brpc_tpu.chaos import injector as _chaos
from incubator_brpc_tpu.observability.profiling import hbm_account, kernel_section
from incubator_brpc_tpu.observability.span import Span
from incubator_brpc_tpu.utils.segmentation import DEVICE_CHUNK_BYTES
from incubator_brpc_tpu.runtime.drain import (
    close_drain,
    current_drain,
    open_drain,
)
from incubator_brpc_tpu.runtime.execution_queue import ExecutionQueue
from incubator_brpc_tpu.metrics.reducer import Adder
from incubator_brpc_tpu.transport import socket as socket_mod
from incubator_brpc_tpu.transport.input_messenger import InputMessenger
from incubator_brpc_tpu.transport.socket import Socket, SocketOptions
from incubator_brpc_tpu.utils.endpoint import EndPoint
from incubator_brpc_tpu.utils.iobuf import IOBuf, DeviceRef, take_handed_off
from incubator_brpc_tpu.utils.logging import log_error

# thread-local delivery burst (see IciFabric.delivery_burst): while a
# burst is open on this thread, queued (non-inline) deliveries collect
# here and each destination port's completion queue wakes ONCE at
# burst close instead of once per frame — the engine.cpp
# flush_pending_burst read-cycle batching, applied to the fabric.
_BURST_TLS = threading.local()

# Frames at or above this size bypass burst capture and wake the
# destination queue immediately: the wake being amortized costs
# microseconds, so holding a bulk frame (milliseconds of parse +
# placement work the receiver could already be overlapping with the
# sender's next placement) until burst close would trade real pipeline
# overlap for nothing.  Coalescing is a small-RPC optimization.
BURST_BYPASS_BYTES = 256 << 10

# HBM heap profiler tag (observability/profiling.py): device payloads
# placed for an in-flight frame (charged from device_put until the
# carrying DeviceRef dies)
_INFLIGHT_ACCT = hbm_account("ici.inflight")

# Nothing adds to these two any more; they read 0 and stay exposed
# only because the benchmark's echo adapter prints them.
ici_pallas_frames = Adder(0).expose("rpc_ici_pallas_frames")
ici_pallas_fallbacks = Adder(0).expose("rpc_ici_pallas_fallbacks")
# Same-chip segments delivered WITHOUT a transmit checksum: they took
# the XLA-copy lane (off-TPU, a dtype outside transfer.KERNEL_DTYPES,
# an untileable shape).  On a TPU moving tileable payloads it stays 0 —
# chip_smoke.py holds it there, so a frame that silently leaves the
# Pallas lane fails the smoke.
ici_unchecked_segments = Adder(0).expose("rpc_ici_unchecked_segments")
# Drain-batch transmits (IciFabric._flush_transmits): the programs that
# copied two or more deferred reply segments at once, and the segments
# they copied.  segments / programs is the batch size achieved.
ici_batch_transmit_programs = Adder(0).expose(
    "rpc_ici_batch_transmit_programs")
ici_batch_transmit_segments = Adder(0).expose(
    "rpc_ici_batch_transmit_segments")


class _Deferred(NamedTuple):
    """A frame whose transmit waits for its drain batch's close."""

    frame: IOBuf
    dst_port: "IciPort"
    src: Tuple[int, int]
    leg: Optional[Span]
    parent: Optional[Tuple[int, int]]
    force: bool
    todo: List[Tuple[Any, Any]]  # (DeviceRef, array) to transmit


class _LazyPeer:
    """Defers _fmt() until a chaos spec actually matches on peer — the
    armed-but-unmatched send path pays no string formatting (the
    injector's raw-object contract), while matchers still see the
    ``sliceN/chipM`` label, not the raw tuple repr."""

    __slots__ = ("coords",)

    def __init__(self, coords):
        self.coords = coords

    def __str__(self):
        return _fmt(self.coords)


def _fmt(coords) -> str:
    """ici://-ish label for span methods: (0, 1) → slice0/chip1."""
    try:
        s, c = coords
        if isinstance(s, int) and isinstance(c, int):
            return f"slice{s}/chip{c}"
        return f"{s}:{c}"
    except Exception:  # noqa: BLE001
        return str(coords)


class IciPort:
    """One endpoint on the fabric (analog RdmaEndpoint). Owns the
    completion queue whose consumer parses frames through the shared
    InputMessenger machinery."""

    def __init__(self, fabric: "IciFabric", coords: Tuple[int, int], server=None, device=None):
        self.fabric = fabric
        self.coords = coords
        self.server = server  # non-None = server port (accepts requests)
        self.device = device  # jax device owning this port's HBM
        self.messenger = InputMessenger()
        # completion queue: frames arrive here (the "CQ polled instead
        # of epoll"); consumer runs on the runtime like ProcessEvent.
        # Each entry's accept stamp is the frame's rpcz received_us; its
        # wait shows as each span's cq_wait phase.
        self._cq = ExecutionQueue(self._drain_completions, stamped=True)
        # receive-window flow control (the RDMA endpoint's sq window /
        # socket _overcrowded analog, rdma_endpoint.h:83-137): bytes
        # delivered but not yet consumed.  A stalled consumer pushes
        # senders into EOVERCROWDED instead of growing the queue
        # without bound.
        self._queued_bytes = 0
        self._qb_lock = threading.Lock()
        self.overcrowded_bytes = 256 << 20
        # per-peer connection sockets (fd-less), keyed by peer coords
        self._conns: Dict[Tuple[int, int], int] = {}
        self._lock = threading.Lock()
        self.closed = False
        # opt-in inline request dispatch (the usercode_in_dispatcher
        # threading model): a local same-process send may run this
        # server port's handlers on the SENDER's thread, trading the
        # non-blocking send guarantee for two fewer task handoffs per
        # RPC — exactly the tradeoff the TCP path's
        # usercode_in_dispatcher makes
        self.inline_dispatch = bool(
            getattr(getattr(server, "options", None),
                    "usercode_in_dispatcher", False)
        )

    # ---- completion processing ---------------------------------------------
    def _drain_completions(self, batch):
        # window credits release ONCE per batch (the RDMA endpoint's
        # completion-batch accounting): senders blocked at
        # EOVERCROWDED wait at most one batch (batch_max frames) longer
        # than per-frame release, and the steady-state drain pays one
        # lock instead of len(batch)
        released = 0
        entries = list(batch)
        # a server port's batch runs in a drain scope (runtime/drain.py):
        # a service may defer commands to the batch's close, where the
        # scope runs them before the window credits go back; a batch of
        # two or more frames also defers this port's small same-chip
        # reply transmits there (IciFabric.send)
        scoped = self.server is not None
        if scoped:
            prev = open_drain(self.coords if len(entries) > 1 else None)
        try:
            for i, ((frame, peer_coords, parent), received_us) in enumerate(
                entries
            ):
                released += len(frame)
                if self.closed:
                    # the finally below releases up to THIS frame; the
                    # undrained rest of the batch would leak its window
                    # bytes (and wedge senders at EOVERCROWDED on a
                    # port reopened at these coords) — count them too
                    released += sum(len(e[0][0]) for e in entries[i + 1:])
                    return
                sock = self._conn_socket(peer_coords)
                if sock is None or sock.failed:
                    continue
                # rpcz stamps: received = deliver accepted the frame (the
                # fabric CQ's epoll-IN analog), dequeued = picked up here;
                # the sender's span ids ride beside the frame for
                # protocols without trace meta (RESP)
                sock.last_read_event_us = received_us
                sock.last_dequeued_us = _time.time_ns() // 1000
                sock.last_read_parent = parent
                sock.read_buf.append(frame)  # ref move, zero-copy
                try:
                    # the SAME cut/dispatch loop as TCP, auth gate
                    # included; parse sees DeviceRefs untouched
                    self.messenger.cut_and_dispatch(sock)
                except Exception as e:  # noqa: BLE001
                    log_error("ici completion processing failed: %r", e)
        finally:
            if scoped:
                try:
                    close_drain(prev)
                except Exception as e:  # noqa: BLE001
                    log_error("ici drain flush failed: %r", e)
            if released:
                with self._qb_lock:
                    self._queued_bytes -= released

    def deliver(self, frame: IOBuf, from_coords: Tuple[int, int],
                inline_ok: bool = False, force: bool = False,
                parent: Optional[Tuple[int, int]] = None) -> bool:
        """Called by the fabric: enqueue a received frame (a completion).

        Server ports and bridge-delivered frames go through the
        completion queue by default: inline dispatch would run user
        service handlers on the SENDER's thread (breaking the
        non-blocking send contract) or block the DCN bridge reader
        mid-stream.  CLIENT ports on a local same-process send may run
        inline (execute_or_inline): response processing is framework
        code plus the done callback, and skipping the queue handoff
        saves one thread wakeup on the sync RPC turnaround — the
        reference likewise runs response processing on the event thread
        that read it (process_response, input_messenger.cpp).  A server
        that opted into ``usercode_in_dispatcher`` extends the same
        inline treatment to request dispatch (``inline_dispatch``).

        Inside a fabric ``delivery_burst`` (ParallelChannel fan-out,
        ``send_batch``), queued deliveries are captured per-port and
        the completion queue wakes once at burst close — except frames
        ≥ BURST_BYPASS_BYTES, which dispatch immediately so bulk
        receive work overlaps the sender's remaining burst.

        ``parent``: the sender's (trace_id, span_id), or None."""
        if self.closed:
            # close raced the fabric's port() lookup: refuse before any
            # credit is reserved (and before a burst could capture a
            # frame that would only be refused — silently — at flush)
            return False
        n = len(frame)
        with self._qb_lock:
            if (
                not force
                and self._queued_bytes + n > self.overcrowded_bytes
            ):
                return False  # receive window full → sender gets
                # EOVERCROWDED (socket.h _overcrowded analog)
            self._queued_bytes += n
        socket_mod.g_in_bytes << n
        item = (frame, from_coords, parent)
        if inline_ok and (self.server is None or self.inline_dispatch):
            if not self._cq.execute_or_inline(item):
                # queue already stopped (close raced the send): the
                # frame will never run — release the reservation and
                # tell the sender, exactly like the queued path below
                with self._qb_lock:
                    self._queued_bytes -= n
                return False
            return True
        pending = getattr(_BURST_TLS, "pending", None)
        if pending is not None and n < BURST_BYPASS_BYTES:
            pending.setdefault(self, []).append(item)
            return True
        if not self._cq.execute(item):
            # queue already stopped (close raced the send): the frame
            # will never drain — give its window bytes back instead of
            # leaking them against a port reopened at these coords
            with self._qb_lock:
                self._queued_bytes -= n
            return False
        return True

    def _flush_burst(self, items: List) -> None:
        """Enqueue a burst's captured deliveries with ONE consumer wake
        (ExecutionQueue.execute_batch).  A stopped queue refuses the
        batch — release those frames' window credits, same reasoning as
        the single-frame path.  The senders were already told 0 at
        capture time, so this close-between-capture-and-flush race
        resolves through their deadlines (the same way an in-flight
        frame lost to a close does on any transport) — deliver()'s
        ``closed`` pre-check keeps the window microscopic, and the drop
        is LOUD here, never silent."""
        if not self._cq.execute_batch(items):
            n = sum(len(it[0]) for it in items)
            with self._qb_lock:
                self._queued_bytes -= n
            log_error(
                "ici port %s closed mid-burst: %d captured frame(s) "
                "dropped; senders recover via deadline", self.coords,
                len(items),
            )

    # ---- connection sockets -------------------------------------------------
    def _conn_socket(self, peer_coords: Tuple[int, int]) -> Optional[Socket]:
        # the whole check-then-create runs under the lock so concurrent
        # callers can't mint duplicate (and leaked) sockets for one peer
        with self._lock:
            sid = self._conns.get(peer_coords)
            if sid is not None:
                sock = Socket.address(sid)
                if sock is not None and not sock.failed:
                    return sock
            sid = Socket.create(
                SocketOptions(
                    fd=None,
                    remote=EndPoint.ici(*peer_coords),
                    messenger=self.messenger,
                    server=self.server,
                )
            )
            sock = Socket.address(sid)
            sock.ici_port = self
            sock.ici_peer_coords = peer_coords
            self._conns[peer_coords] = sid
            return sock

    def connect(self, peer_coords: Tuple[int, int]):
        """Client-side: SocketId for the connection to peer coords,
        or None (note: 0 is a valid SocketId — the first pool slot)."""
        sock = self._conn_socket(peer_coords)
        return sock.sid if sock is not None else None

    def close(self):
        self.closed = True
        self._cq.stop()
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for sid in conns:
            s = Socket.address(sid)
            if s is not None:
                s.set_failed(errors.ECLOSE, "ici port closed")


class IciFabric:
    """The interconnect: routes frames between registered ports and
    places device payload onto the destination's device (the XLA
    device-to-device transfer; a no-op when src and dst share a chip)."""

    def __init__(self):
        self._ports: Dict[Tuple[int, int], IciPort] = {}
        self._lock = threading.Lock()
        # False (default): same-chip delivery runs every device segment
        # through the transmit op (ops/transfer.transmit_array_chunked) so
        # the payload demonstrably traverses HBM once per hop — the
        # honest model of an ICI transmission (a segment its producer
        # handed off, iobuf.hand_off, made that traversal already and
        # moves by reference). True: move by reference (the in-process
        # fast path; no device bytes move).
        self.zero_copy = False
        # chunk size of the fused chunk program that frames of
        # MIN_CHUNKS chunks or more take (utils/segmentation.py)
        self.chunk_bytes = DEVICE_CHUNK_BYTES

    @contextlib.contextmanager
    def delivery_burst(self):
        """Coalesce this thread's queued fabric deliveries: while the
        context is open, each destination port collects frames in a
        pending list and its completion queue wakes ONCE at close
        (engine.cpp flush_pending_burst read-cycle batching).  Inline
        deliveries are unaffected (they never wake anything).  Nested
        bursts join the outermost one.

        Do NOT block on a fabric response inside the burst — the
        request may be sitting in the un-flushed pending list."""
        if getattr(_BURST_TLS, "pending", None) is not None:
            yield  # nested: the outer burst flushes
            return
        pending: Dict[IciPort, List] = {}
        _BURST_TLS.pending = pending
        try:
            yield
        finally:
            _BURST_TLS.pending = None
            for port, items in pending.items():
                port._flush_burst(items)

    def send_batch(
        self,
        frames,
        dst: Tuple[int, int],
        src: Tuple[int, int],
        zero_copy: Optional[bool] = None,
        ignore_eovercrowded: bool = False,
    ) -> List[int]:
        """Ship several frames to one destination with amortized
        window/credit bookkeeping: per-frame placement and admission
        semantics are identical to ``send``, but the destination's
        completion queue wakes once for the whole batch.  Returns one
        rc per frame (a frame that faults mid-batch fails alone — its
        window credits never linger)."""
        with self.delivery_burst():
            return [
                self.send(
                    f, dst, src, zero_copy=zero_copy,
                    ignore_eovercrowded=ignore_eovercrowded,
                )
                for f in frames
            ]

    def register(self, coords: Tuple[int, int], server=None, device=None) -> IciPort:
        if device is not None:
            # the transmit op's module (Pallas) takes most of a second to
            # import: load it with the port, not inside the first send,
            # where the caller's RPC deadline is already running
            import incubator_brpc_tpu.ops.transfer  # noqa: F401
        with self._lock:
            if coords in self._ports and not self._ports[coords].closed:
                raise ValueError(f"ici coords {coords} already registered")
            port = IciPort(self, coords, server=server, device=device)
            self._ports[coords] = port
            return port

    def unregister(self, coords: Tuple[int, int]):
        with self._lock:
            port = self._ports.pop(coords, None)
        if port is not None:
            port.close()

    def port(self, coords: Tuple[int, int]) -> Optional[IciPort]:
        port = self._ports.get(coords)
        return port if port is not None and not port.closed else None

    def send(
        self,
        frame: IOBuf,
        dst: Tuple[int, int],
        src: Tuple[int, int],
        zero_copy: Optional[bool] = None,
        _local_only: bool = False,
        ignore_eovercrowded: bool = False,
    ) -> int:
        """Ship a frame. Device segments are re-placed onto the dst
        device if it differs (jax.device_put = the ICI/DCN hop);
        same-device segments traverse HBM through the Pallas transmit
        op unless zero_copy — then they move by reference. Coords not
        registered in this process route over the DCN bridge
        (parallel/dcn.py), the RDMA-TCP-bootstrap analog.

        A frame sent from a server port while that port drains a batch
        of two or more frames on this thread defers the segments the
        whole-frame kernel would take (``transfer.batchable``) to the
        batch's close (``_flush_transmits``), where it is delivered; it
        counts as sent.  Any other frame from that port delivers what
        was deferred first, so a connection keeps its order."""
        dst_port = self.port(dst)
        scope = current_drain()
        if scope is not None and (_local_only or scope.owner != src):
            scope = None
        if dst_port is None:
            if not _local_only:
                from incubator_brpc_tpu.parallel.dcn import get_bridge

                route = get_bridge().route(dst)
                if route is not None:
                    if scope is not None:
                        scope.flush_one(self._flush_transmits)
                    # the DCN bridge records its own collective leg span
                    rc = route.send_frame(frame, dst, src)
                    if rc == 0:
                        socket_mod.g_out_bytes << len(frame)
                        socket_mod.g_out_messages << 1
                    return rc
            return errors.EFAILEDSOCKET
        close_after_deliver = False
        if _chaos.armed:
            spec = _chaos.check("ici.send", peer=_LazyPeer(dst))
            if spec is not None:
                act = spec.action
                if act == "drop":
                    # the leg silently vanishes (an in-flight hop lost
                    # on the fabric): callers recover via deadlines
                    return 0
                if act == "delay_us":
                    _chaos.sleep_us(spec.arg)
                elif act == "reset":
                    return errors.EFAILEDSOCKET
                elif act == "close_mid_batch":
                    # deliver THIS frame, then close the destination
                    # port so its completion-queue drain observes the
                    # close mid-batch (the receive-window release path)
                    close_after_deliver = True
        # rpcz collective sub-span: one ICI leg (placement + delivery),
        # parented to the active RPC span so fan-out traces show every
        # per-chip hop (skipped entirely outside a traced RPC)
        leg = Span.create_collective("ici", f"{_fmt(src)}->{_fmt(dst)}")
        parent = None
        if leg is not None:
            leg.request_size = len(frame)
            parent = (leg.trace_id, leg.parent_span_id)
        try:
            try:
                todo = None
                if dst_port.device is not None:
                    zc = self.zero_copy if zero_copy is None else zero_copy
                    todo = self._place_segments(
                        frame, dst_port, zc,
                        defer=scope is not None and not close_after_deliver,
                    )
            except BaseException as e:
                # close the leg with an error first: the trace must
                # show the hop that failed, not silently lose it
                if leg is not None:
                    leg.end(errors.EINTERNAL)
                if isinstance(e, Exception):
                    # a fault mid-placement (chunk k of a chunked
                    # pipeline, a bad dtype, an injected ici.chunk
                    # reset) happens BEFORE any window credit is
                    # reserved — deliver has not run — so failing the
                    # frame here surfaces ONE ERPC error to the sender
                    # and leaks nothing
                    log_error("ici send %s->%s failed: %r", src, dst, e)
                    return errors.EINTERNAL
                raise
            if todo:
                socket_mod.g_out_bytes << len(frame)
                socket_mod.g_out_messages << 1
                scope.defer(self._flush_transmits, _Deferred(
                    frame, dst_port, src, leg, parent, ignore_eovercrowded,
                    todo,
                ))
                return 0
            if leg is not None:
                # placement and transmit dispatch done; delivery (inline:
                # the whole receive side) not begun
                leg.placed_us = _time.time_ns() // 1000
            if not _local_only:
                # bridged inbound frames (_local_only) are RECEIVED
                # traffic; counting them here would inflate the
                # outbound metrics
                socket_mod.g_out_bytes << len(frame)
                socket_mod.g_out_messages << 1
            if scope is not None:
                scope.flush_one(self._flush_transmits)
            try:
                delivered = dst_port.deliver(
                    frame, src, inline_ok=not _local_only,
                    force=ignore_eovercrowded, parent=parent,
                )
            except BaseException:
                # deliver may have reserved window credits before the
                # failure (a raising spawn leaves the frame queued for
                # the close-time drain) — do NOT relabel this as a
                # clean per-frame EINTERNAL; propagate so the anomaly
                # stays loud
                if leg is not None:
                    leg.end(errors.EINTERNAL)
                raise
        finally:
            # an injected close must happen however delivery went
            # (success, window-full, raise): the spec's hit budget is
            # already consumed, so skipping here would record a close
            # that never happened
            if close_after_deliver:
                dst_port.close()
        if not delivered:
            # distinguish WHY delivery was refused: a closed port (or
            # its stopped completion queue) is a dead destination and
            # must read as a connection failure, not as transient
            # receive-window backpressure — retry/circuit-breaker
            # accounting keys on the difference
            rc = (
                errors.EFAILEDSOCKET
                if dst_port.closed
                else errors.EOVERCROWDED
            )
            if leg is not None:
                leg.end(rc)
            return rc
        if leg is not None:
            leg.end(0)
        return 0

    def _flush_transmits(self, items) -> None:
        """A drain batch's deferred frames, at its close: every deferred
        segment through one batch program a shape
        (``transfer.transmit_batch``), then each frame delivered in
        arrival order, as ``send`` would have.  Each leg starts at the
        flush and is placed at the program's return: the shared program
        is charged whole to every member.  The senders were told 0, so
        a frame refused here (a closed port, a full window, a failed
        program) ends its leg with the error and is lost like a frame
        in flight: its caller recovers through its deadline."""
        from incubator_brpc_tpu.ops.transfer import transmit_batch

        start = _time.time_ns() // 1000
        segs = [ref_arr for it in items for ref_arr in it.todo]
        try:
            placed, sizes = transmit_batch([arr for _, arr in segs])
        except Exception as e:  # noqa: BLE001
            log_error("ici batch transmit of %d frame(s) failed: %r",
                      len(items), e)
            for it in items:
                if it.leg is not None:
                    it.leg.end(errors.EINTERNAL)
            return
        if sizes:
            ici_batch_transmit_programs << len(sizes)
            ici_batch_transmit_segments << sum(sizes)
        for (ref, _), (arr, csum) in zip(segs, placed):
            ref.array, ref.csum = arr, csum
        placed_us = _time.time_ns() // 1000
        refused = 0
        for frame, dst_port, src, leg, parent, force, _ in items:
            if leg is not None:
                leg.start_us, leg.placed_us = start, placed_us
            try:
                delivered = dst_port.deliver(
                    frame, src, inline_ok=True, force=force, parent=parent,
                )
            except Exception as e:  # noqa: BLE001
                log_error("ici deferred delivery to %s failed: %r",
                          dst_port.coords, e)
                delivered = None
            if not delivered:
                refused += 1
            if leg is not None:
                leg.end(
                    0 if delivered
                    else errors.EINTERNAL if delivered is None
                    else errors.EFAILEDSOCKET if dst_port.closed
                    else errors.EOVERCROWDED
                )
        if refused:
            log_error("ici drain batch: %d deferred frame(s) refused at "
                      "delivery; senders recover via deadline", refused)

    def local_server_coords(self):
        """Server ports registered in THIS process (what the DCN hello
        advertises to peers)."""
        with self._lock:
            items = list(self._ports.items())
        return sorted(
            coords
            for coords, port in items
            if not port.closed
            and port.server is not None
            and isinstance(coords[0], int)
            and isinstance(coords[1], int)
        )

    def server_coords(self):
        """Every reachable server port: local ones plus those learned
        over DCN bridges (the tpu:// naming service reads this, so a
        cross-process cluster resolves like a local one)."""
        coords = set(self.local_server_coords())
        from incubator_brpc_tpu.parallel.dcn import _bridge

        if _bridge is not None:
            coords.update(
                c
                for c in _bridge.remote_server_coords()
                if isinstance(c[0], int) and isinstance(c[1], int)
            )
        return sorted(coords)

    def routable(self, coords) -> bool:
        """True if coords are a local port or reachable over a bridge."""
        if self.port(coords) is not None:
            return True
        from incubator_brpc_tpu.parallel.dcn import _bridge

        return _bridge is not None and _bridge.route(coords) is not None

    def _place_segments(self, frame: IOBuf, dst_port: IciPort,
                        zero_copy: bool, defer: bool = False):
        """Place ``frame``'s device segments for ``dst_port``.  With
        ``defer``, the segments a drain batch may transmit together are
        left as they are and returned as ``[(ref, array), ...]``."""
        import jax

        device = dst_port.device
        todo = []
        for ref in frame.device_segments():
            arr = ref.whole_array()
            if arr is None:
                continue  # split segment: materialized as bytes downstream
            src_devs = getattr(arr, "devices", lambda: set())()
            if device not in src_devs:
                with kernel_section("ici.place"):
                    ref.array = jax.device_put(arr, device)
                # in-flight ledger: the placed payload is the frame's
                # HBM until the carrying ref dies (receiver adoption —
                # e.g. the cache store — charges its own tag)
                charged = _INFLIGHT_ACCT.adopt(ref.array)
                if charged:
                    weakref.finalize(ref, _INFLIGHT_ACCT.release, charged)
            elif not zero_copy and not take_handed_off(arr):
                # same-chip hop: the payload traverses HBM once through
                # the copy+checksum kernel — receiver gets a fresh
                # buffer plus a device-resident integrity checksum (a
                # handed-off buffer, a cache slab read's row slice,
                # already traversed HBM when its program made it)
                if defer:
                    from incubator_brpc_tpu.ops.transfer import batchable

                    if batchable(arr, self.chunk_bytes):
                        todo.append((ref, arr))
                        continue
                ref.array, ref.csum = self._transmit_segment(arr, dst_port)
                if ref.csum is None:
                    ici_unchecked_segments << 1
        return todo

    def _transmit_segment(self, arr, dst_port: IciPort):
        """One device segment through the transmit op; the op picks the
        whole-frame kernel or the fused chunk program by size
        (ops/transfer.transmit_array_chunked, docs/ici_pipeline.md)."""
        from incubator_brpc_tpu.ops.transfer import transmit_array_chunked

        walk = None
        if _chaos.armed:
            # the fused program is ONE dispatch, so the per-chunk
            # ici.chunk site is walked over its plan before dispatch
            def walk(total_chunks):
                self._chaos_walk_chunks(total_chunks, dst_port)

        return transmit_array_chunked(arr, self.chunk_bytes, walk=walk)

    @staticmethod
    def _chaos_walk_chunks(total_chunks: int, dst_port: IciPort):
        """Walk every planned chunk through the ici.chunk site (armed
        plans only).  reset abandons the frame mid-stream — send() turns
        it into ONE ERPC error, and no window credit was reserved yet,
        so nothing leaks (regression-tested under a seeded FaultPlan);
        delay_us stretches one chunk's stage."""
        for k in range(total_chunks):
            spec = _chaos.check("ici.chunk", peer=_LazyPeer(dst_port.coords))
            if spec is None:
                continue
            if spec.action == "delay_us":
                _chaos.sleep_us(spec.arg)
            elif spec.action == "reset":
                raise ConnectionResetError(
                    f"chaos: ici chunk {k}/{total_chunks} reset"
                )


_fabric: Optional[IciFabric] = None
_fabric_lock = threading.Lock()


def get_fabric() -> IciFabric:
    global _fabric
    if _fabric is None:
        with _fabric_lock:
            if _fabric is None:
                _fabric = IciFabric()
    return _fabric


import itertools as _itertools
import os as _os

_client_port_seq = _itertools.count(1)


def acquire_client_port(device=None) -> IciPort:
    """Register a uniquely-keyed client port (shared helper for
    Channel and LoadBalancerWithNaming). Keys carry the pid so client
    ports of DIFFERENT processes bridged to one server can't collide in
    its DCN reply-routing table."""
    return get_fabric().register(
        ("client", f"{_os.getpid()}-{next(_client_port_seq)}"),
        server=None,
        device=device,
    )
