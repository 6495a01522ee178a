"""DCN bridge — the cross-process/cross-host leg of the ICI fabric.

Analog of the reference RDMA endpoint's TCP-assisted bootstrap
(rdma/rdma_endpoint.h:93-108 handshake state machine, rdma_helper
global init): a TCP side channel carries the fabric hello and every
fabric frame between processes.

Bulk path (v2, the RDMA endpoint's windowed send queue analog,
rdma_endpoint.h:83-137):
- device→host staging of ALL device segments starts up front
  (``copy_to_host_async`` fires every D2H DMA before the first wire
  byte moves);
- a stager thread slices segment bytes into ~2MB wire chunks and feeds
  them through a BOUNDED queue (the send window, default 8 chunks =
  16MB) to the socket writer — staging of segment k+1 overlaps the
  kernel send of segment k;
- the receiver streams each segment off the socket and hands completed
  device segments to an upload worker, so host→device re-placement of
  segment k overlaps the read of segment k+1.  (Within a SINGLE device
  segment the upload still waits for its full bytes: per-chunk device
  uploads would pay one host-to-device round trip per chunk instead of
  one for the segment.)

The wire format is unchanged from v1 — chunking is purely a local
pipelining strategy, so mixed-version bridges interoperate.

Topology flow:
- server process: ``listen_dcn(port)`` — accepts bridge connections.
- client process: ``connect_dcn(host, port)`` — handshake learns the
  remote fabric's server coords; the local fabric records them as
  remote routes, so ``tpu://`` naming resolves them and
  ``IciFabric.send`` ships frames over the bridge transparently.
- reverse path: a frame's src coords are learned as a route back
  through the connection it arrived on (client ports are created
  lazily, so they cannot be advertised in the hello).

Wire format (all big-endian):
- hello:      b"ICI1" u32(len) json{role, server_coords:[[s,c]..]}
- hello-ack:  same shape from the acceptor
- frame:      b"ICIF" u32(len) json{src, dst, segs:[{k,"n",dtype?,shape?}..]}
              followed by the segments' raw bytes in order
  seg kind "b" = host bytes; "d" = a whole device array (dtype/shape
  re-materialize it on the receiving side).
"""

from __future__ import annotations

import json
import queue as _queue
import select as _select
import socket as _pysocket
import ssl as _ssl
import struct
import threading
from typing import Dict, List, Optional, Tuple

from incubator_brpc_tpu.chaos import injector as _chaos
from incubator_brpc_tpu.observability.span import Span
from incubator_brpc_tpu.utils.segmentation import (
    WIRE_CHUNK_BYTES,
    chunk_buffer,
    chunk_views,
)
from incubator_brpc_tpu.utils.iobuf import DeviceRef, IOBuf
from incubator_brpc_tpu.utils.logging import log_error, log_info

_HELLO_MAGIC = b"ICI1"
_FRAME_MAGIC = b"ICIF"
_MAX_HEADER = 16 << 20
# ~4MB wire chunks (RDMA endpoint frame granularity) — the SHARED
# segmentation policy (utils/segmentation.py), same planner the ICI
# chunked transmit and the kernel-socket write loop use
_WIRE_CHUNK = WIRE_CHUNK_BYTES
_SEND_WINDOW = 8  # staged-but-unsent chunks allowed in flight (32MB)


def _coords_to_wire(coords) -> list:
    return list(coords)


def _coords_from_wire(raw, server: bool = False) -> Optional[Tuple]:
    """Validate peer-supplied coords. Port keys are 2-tuples: servers
    are (slice:int, chip:int); client ports are ("client", "pid-seq").
    Anything else is dropped — a malformed peer must not crash the
    naming service or fabric that later consumes these."""
    try:
        if len(raw) != 2:
            return None
        s, c = raw
    except TypeError:
        return None
    ok_types = (int,) if server else (int, str)
    if isinstance(s, bool) or isinstance(c, bool):
        return None
    if not isinstance(s, ok_types) or not isinstance(c, ok_types):
        return None
    return (s, c)


def _plan_frame(frame: IOBuf, src, dst):
    """Plan the wire encoding of an IOBuf: returns (header_bytes,
    producers, total_payload_bytes) where each producer() yields the
    corresponding segment's payload as memoryview chunks of
    ≤ _WIRE_CHUNK bytes.

    Every whole-array device segment's D2H DMA is kicked off HERE via
    ``copy_to_host_async`` — all device transfers run concurrently with
    each other and with the socket writes of earlier segments."""
    segs = []
    producers = []
    pending_host: List[memoryview] = []  # views into `frame` (alive
    # for the whole send): staging copies nothing

    # chunking comes from the shared segmentation policy
    # (utils/segmentation.py): chunk_buffer for contiguous staging
    # buffers, chunk_views for ref lists
    def flush_host():
        if pending_host:
            views = list(pending_host)
            segs.append({"k": "b", "n": sum(len(v) for v in views)})
            producers.append(
                lambda views=views: chunk_views(views, _WIRE_CHUNK)
            )
            pending_host.clear()

    for ref in frame._refs:
        if isinstance(ref, DeviceRef):
            arr = ref.whole_array()
            if arr is not None:
                flush_host()
                import numpy as np

                if hasattr(arr, "copy_to_host_async"):
                    try:
                        arr.copy_to_host_async()  # start the DMA now
                    except Exception:  # noqa: BLE001 — fetch still works
                        pass
                dtype = np.dtype(arr.dtype)
                shape = tuple(arr.shape)
                nbytes = int(dtype.itemsize)
                for d in shape:
                    nbytes *= int(d)
                segs.append(
                    {
                        "k": "d",
                        "n": nbytes,
                        "dtype": str(dtype),
                        "shape": list(shape),
                    }
                )

                def produce(arr=arr):
                    import numpy as np

                    from incubator_brpc_tpu.analysis.device_witness import (
                        allowed_transfer,
                    )

                    # the DCN bridge IS the device/host boundary: the
                    # segment must become contiguous host bytes to hit
                    # the socket (manifested as dcn.wire)
                    with allowed_transfer("dcn.wire"):
                        host = np.ascontiguousarray(np.asarray(arr))
                    return chunk_buffer(
                        host.view(np.uint8).reshape(-1), _WIRE_CHUNK
                    )

                producers.append(produce)
                continue
            # split device segment: ship its byte window as host bytes
        pending_host.append(ref.view())  # already a memoryview
    flush_host()
    header = json.dumps(
        {"src": _coords_to_wire(src), "dst": _coords_to_wire(dst), "segs": segs}
    ).encode()
    return header, producers, sum(s["n"] for s in segs)


_warmed = False
_warm_lock = threading.Lock()


def _warm_bulk_path():
    """One-time per-process warmup of everything a first bulk frame
    would otherwise pay inline (the measured 0.403s first-64MB-echo
    straggler, BENCH_r05 dcn_64mb_echo_s_all):

    - pre-touch a wire-chunk-sized receive buffer so the allocator
      arenas the first ``recv_into`` faults into are already mapped;
    - run one tiny host→device upload, because the first
      ``jnp.asarray`` in a fresh process pays the whole jax platform
      init — by far the biggest share of the straggler — inside the
      reader's upload worker.

    Runs on a daemon thread off listen()/connect(); jax-free processes
    simply skip the upload half."""
    global _warmed
    with _warm_lock:
        if _warmed:
            return
        _warmed = True
    try:
        import numpy as np

        buf = np.empty(_WIRE_CHUNK, dtype=np.uint8)
        buf[::4096] = 0  # fault every page in
        del buf
    except ImportError:
        bytearray(_WIRE_CHUNK)  # zeroing touches every page
    try:
        import jax.numpy as jnp
        import numpy as np

        jnp.asarray(np.ones(8, dtype=np.float32)).block_until_ready()
    except Exception:  # noqa: BLE001 — no jax here: uploads keep bytes
        pass


def _spawn_warmup():
    if not _warmed:
        threading.Thread(
            target=_warm_bulk_path, daemon=True, name="dcn-warmup"
        ).start()


def _recv_exact(conn, n: int) -> Optional[bytes]:
    out = bytearray()
    while len(out) < n:
        chunk = conn.recv(min(1 << 20, n - len(out)))
        if not chunk:
            return None
        out += chunk
    return bytes(out)


def _read_header(conn) -> Optional[Tuple[bytes, dict]]:
    """Read one message's magic + JSON header (shared by the handshake
    reader and the streaming frame loop). → (magic, header) or None on
    EOF/garbage."""
    head = _recv_exact(conn, 8)
    if head is None:
        return None
    magic, hlen = head[:4], struct.unpack(">I", head[4:])[0]
    if magic not in (_HELLO_MAGIC, _FRAME_MAGIC) or hlen > _MAX_HEADER:
        return None
    raw = _recv_exact(conn, hlen)
    if raw is None:
        return None
    try:
        header = json.loads(raw)
    except ValueError:
        return None
    return magic, header


def _read_message(conn) -> Optional[Tuple[bytes, dict, bytes]]:
    """→ (magic, header_json, body) or None on EOF/garbage.  Handshake
    use only — frame bodies are drained whole here, not streamed."""
    msg = _read_header(conn)
    if msg is None:
        return None
    magic, header = msg
    body = b""
    if magic == _FRAME_MAGIC:
        total = sum(s["n"] for s in header.get("segs", ()))
        body = _recv_exact(conn, total)
        if body is None:
            return None
    return magic, header, body


class _LockedTlsSocket:
    """Serializes all I/O on one TLS bridge connection.

    OpenSSL's ``SSL*`` is not thread-safe for simultaneous
    SSL_read/SSL_write and CPython's ``_ssl`` adds no per-object lock,
    yet the bridge reads (reader_loop) and writes (send_frame) from
    different threads on the same connection.  Every SSL call holds one
    lock.  Reads do a non-blocking probe under the lock and then park
    in select() OUTSIDE it, so an idle reader costs no SSL/lock churn
    and never starves the writer.  Writes go out in bounded chunks with
    a per-chunk timeout, so a wedged peer fails the send (send_frame
    then closes the bridge) instead of holding the lock forever.
    Plaintext connections bypass this class entirely (kernel sockets
    are full-duplex safe).
    """

    _CHUNK = 64 << 10
    _SEND_TIMEOUT_S = 20.0  # floor rate ~3 KB/s before we declare wedged
    _PARK_S = 0.5

    def __init__(self, sock: _ssl.SSLSocket):
        self._sock = sock
        self._lock = threading.Lock()

    def sendall(self, data) -> None:
        mv = memoryview(data)
        if not len(mv):
            return
        for off in range(0, len(mv), self._CHUNK):
            with self._lock:
                self._sock.settimeout(self._SEND_TIMEOUT_S)
                self._sock.sendall(mv[off : off + self._CHUNK])

    def _recv_op(self, op):
        while True:
            with self._lock:
                self._sock.settimeout(0)  # instant probe: never parks
                try:
                    return op()
                except (
                    _ssl.SSLWantReadError,
                    _ssl.SSLWantWriteError,  # renegotiation mid-read
                    BlockingIOError,
                ):
                    pass
            # park OUTSIDE the lock: select on the fd is safe alongside
            # a concurrent SSL_write, unlike a blocking SSL_read
            _select.select([self._sock], [], [], self._PARK_S)

    def recv(self, n: int) -> bytes:
        return self._recv_op(lambda: self._sock.recv(n))

    def recv_into(self, view, nbytes: int = 0) -> int:
        return self._recv_op(lambda: self._sock.recv_into(view, nbytes))

    def settimeout(self, t) -> None:  # timeouts are managed per-call
        pass

    def close(self) -> None:
        self._sock.close()


class _BridgeConn:
    """One established bridge connection (either direction)."""

    def __init__(self, bridge: "DcnBridge", conn: _pysocket.socket, peer: str):
        if isinstance(conn, _ssl.SSLSocket):
            conn = _LockedTlsSocket(conn)
        else:
            # deep kernel buffers: bulk frames move in multi-MB chunks,
            # and the default ~208KB socket buffers force one syscall
            # per ~200KB on the receive side (best-effort; the kernel
            # clamps to its rmem/wmem limits)
            try:
                conn.setsockopt(
                    _pysocket.SOL_SOCKET, _pysocket.SO_SNDBUF, 8 << 20
                )
                conn.setsockopt(
                    _pysocket.SOL_SOCKET, _pysocket.SO_RCVBUF, 8 << 20
                )
            except OSError:
                pass
        self.bridge = bridge
        self.conn = conn
        self.peer = peer
        self._send_lock = threading.Lock()
        self.closed = False
        self.primed_seen = False  # peer's priming frame arrived
        # chaos "reorder": one held-back frame swapped with its successor
        self._chaos_stash = None
        self._chaos_stash_gen = 0  # ties each backstop timer to ITS stash
        self._chaos_stash_lock = threading.Lock()

    def send_prime(self) -> None:
        """Priming exchange, half of the straggler fix: a zero-segment
        frame sent right after the handshake exercises the peer's whole
        receive path (magic/header read, JSON parse, reader-loop warm)
        before the first real bulk frame, and its arrival proves the
        link full-duplex.  The receiver skips it via the ``prime``
        header key; peers that predate the key would try to route it
        and log one dropped-frame line — wire framing stays intact
        either way."""
        header = json.dumps(
            {"prime": 1, "src": [-1, -1], "dst": [-1, -1], "segs": []}
        ).encode()
        try:
            with self._send_lock:
                self.conn.sendall(
                    _FRAME_MAGIC + struct.pack(">I", len(header)) + header
                )
        except OSError:
            pass  # the reader loop will notice a genuinely dead conn

    def send_frame(self, frame: IOBuf, dst, src) -> int:
        from incubator_brpc_tpu import errors

        if _chaos.armed:
            spec = _chaos.check("dcn.send", peer=self.peer)
            if spec is not None:
                act = spec.action
                if act == "drop":
                    return 0  # frame vanishes on the wide-area hop
                if act == "delay_us":
                    _chaos.sleep_us(spec.arg)
                elif act == "reset":
                    # bridge disconnect mid-traffic: the reader loop
                    # sees EOF and the routing table drops this conn
                    self.close()
                    return errors.EFAILEDSOCKET
                elif act == "reorder":
                    with self._chaos_stash_lock:
                        if self._chaos_stash is None:
                            # hold this frame; it ships AFTER the next
                            # frame on this conn (frame reordering on
                            # the DCN path, deterministic swap).  A
                            # timer backstop flushes it if no successor
                            # ever comes — "reorder" must never degrade
                            # into a silent permanent drop
                            self._chaos_stash = (frame, dst, src)
                            self._chaos_stash_gen += 1
                            gen = self._chaos_stash_gen
                            from incubator_brpc_tpu.runtime.timer_thread import (
                                get_timer_thread,
                            )

                            get_timer_thread().schedule(
                                self._chaos_flush_stash, 0.2, gen
                            )
                            return 0
        stashed = None
        if self._chaos_stash is not None:
            with self._chaos_stash_lock:
                stashed, self._chaos_stash = self._chaos_stash, None
        rc = self._send_frame_now(frame, dst, src)
        if stashed is not None:
            self._send_stashed(*stashed)
        return rc

    def _send_stashed(self, frame, dst, src):
        """Ship a reorder-held frame; a failure here has no caller to
        return to, so it must at least be LOUD (the hold-back comment
        promises reorder never degrades into a silent drop)."""
        rc = self._send_frame_now(frame, dst, src)
        if rc:
            log_error(
                "dcn chaos reorder: held frame for %s lost on re-send "
                "(rc=%s)", dst, rc,
            )

    def _chaos_flush_stash(self, gen):
        """Timer backstop: ship a reorder-held frame that never got a
        successor to swap with (runs spawned off the timer thread —
        send_frame can block on the socket).  The generation check
        drops a stale timer whose stash was already swapped out —
        without it, the timer of stash A would flush a LATER stash C
        early, turning a deterministic swap into a timing-dependent
        plain delay."""
        with self._chaos_stash_lock:
            if gen != self._chaos_stash_gen:
                return
            stashed, self._chaos_stash = self._chaos_stash, None
        if stashed is not None and not self.closed:
            from incubator_brpc_tpu.runtime import scheduler

            scheduler.spawn(self._send_stashed, *stashed)

    def _send_frame_now(self, frame: IOBuf, dst, src) -> int:
        from incubator_brpc_tpu import errors

        # rpcz collective sub-span: the cross-host leg of this frame
        # (parented to the active RPC span; None outside a traced RPC)
        leg = Span.create_collective("dcn", f"{src}->{dst} via {self.peer}")
        if leg is not None:
            leg.request_size = len(frame)
            leg.remote_side = self.peer

        def _done(rc: int) -> int:
            if leg is not None:
                leg.end(rc)
            return rc

        # Planning failures are LOCAL — no wire byte moved, the bridge
        # stays healthy and only this frame fails.
        try:
            header, producers, total = _plan_frame(frame, src, dst)
        except Exception as e:  # noqa: BLE001
            log_error("dcn frame to %s unserializable: %r", self.peer, e)
            return _done(errors.EREQUEST)
        if total > (2 << 30):
            # mirror of the receiver's cap: failing here keeps the
            # bridge alive; streaming it would kill the peer's reader
            log_error("dcn frame to %s too large: %d bytes", self.peer, total)
            return _done(errors.EREQUEST)
        # Once the header is on the wire the stream is committed: ANY
        # failure (socket or stager) desyncs the framing → close.
        try:
            with self._send_lock:
                self.conn.sendall(
                    _FRAME_MAGIC + struct.pack(">I", len(header)) + header
                )
                if producers:
                    self._stream_payloads(producers, leg)
            return _done(0)
        except Exception as e:  # noqa: BLE001 — stager errors included
            log_error("dcn send to %s failed: %r", self.peer, e)
            self.close()
            return _done(errors.EFAILEDSOCKET)

    def _stream_payloads(self, producers, leg=None):
        """Windowed overlap: a stager thread fills a bounded queue with
        wire chunks (staging = D2H fetch + slicing) while this thread
        drains it into the socket.  The queue bound IS the send window
        (reference rdma_endpoint.h:83-137 sq window).  ``leg`` (the
        rpcz collective sub-span) gets a timestamped mark per wire
        chunk, so /rpcz shows the staging/write overlap."""
        nchunk = [0]

        def mark_sent(chunk):
            if leg is not None:
                leg.chunk_mark("dcn wire", nchunk[0], 0, len(chunk))
            nchunk[0] += 1

        if len(producers) == 1:
            # single segment: stage inline (a thread would add handoff
            # cost with nothing to overlap — the fetch happened above)
            for chunk in producers[0]():
                self.conn.sendall(chunk)
                mark_sent(chunk)
            return
        q: _queue.Queue = _queue.Queue(maxsize=_SEND_WINDOW)

        def stage():
            try:
                for p in producers:
                    for chunk in p():
                        q.put(chunk)
                q.put(None)
            except Exception as e:  # noqa: BLE001 — surfaced to writer
                q.put(e)

        t = threading.Thread(target=stage, daemon=True, name="dcn-stager")
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    raise item
                self.conn.sendall(item)
                mark_sent(item)
        finally:
            # unblock a stager stuck on a full window if we bailed early
            while t.is_alive():
                try:
                    q.get_nowait()
                except _queue.Empty:
                    t.join(0.05)

    def _receive_frame_body(self, header):
        """Stream segment payloads off the socket; completed device
        segments upload host→device on worker threads WHILE later
        segments are still arriving. Returns (frame, src, dst)."""
        segs = header.get("segs", ())
        sizes = [int(s["n"]) for s in segs]
        # per-segment validation: a negative size could offset the sum
        # below the cap while another segment demands a huge allocation
        if any(n < 0 for n in sizes):
            raise ValueError("negative segment size")
        total = sum(sizes)
        if total > (2 << 30):
            raise ValueError(f"frame body too large: {total}")
        slots: List = [None] * len(segs)  # bytes | (thread,) placeholder
        uploads: List[threading.Thread] = []

        def upload(i, seg, buf):
            try:
                import jax.numpy as jnp
                import numpy as np

                arr = np.frombuffer(buf, dtype=seg["dtype"]).reshape(
                    seg["shape"]
                )
                slots[i] = ("dev", jnp.asarray(arr))
            except Exception:  # noqa: BLE001 — no jax here: keep the bytes
                slots[i] = ("host", buf)

        for i, seg in enumerate(segs):
            n = int(seg["n"])
            # np.empty skips the memset a bytearray(n) pays — zeroing a
            # 64MB receive buffer costs ~10ms per leg on this class of
            # host, and every byte is overwritten by recv_into anyway
            try:
                import numpy as _np

                buf = _np.empty(n, dtype=_np.uint8)
            except ImportError:  # numpy-less: plain (zeroed) bytearray
                buf = bytearray(n)
            view = memoryview(buf)
            got = 0
            while got < n:
                r = self.conn.recv_into(
                    view[got:], min(_WIRE_CHUNK, n - got)
                )
                if r == 0:
                    raise ConnectionError("peer closed mid-frame")
                got += r
            if seg["k"] == "d":
                t = threading.Thread(
                    target=upload, args=(i, seg, buf), daemon=True,
                    name="dcn-upload",
                )
                t.start()
                uploads.append(t)
            else:
                slots[i] = ("host", buf)
        for t in uploads:
            t.join()
        frame = IOBuf()
        for slot in slots:
            kind, val = slot
            if kind == "dev":
                frame.append_device(val)
            else:
                # zero-copy: the bytearray is owned solely by this
                # frame from here on (append() would memcpy it again)
                frame.append_user_data(val)
        src = _coords_from_wire(header["src"])
        dst = _coords_from_wire(header["dst"])
        if src is None or dst is None:
            raise ValueError("malformed frame coords")
        return frame, src, dst

    def reader_loop(self):
        """Frames from the peer: learn reverse routes, deliver locally."""
        from incubator_brpc_tpu.parallel.ici import get_fabric

        fabric = get_fabric()
        while not self.closed:
            msg = _read_header(self.conn)
            if msg is None:
                break
            magic, header = msg
            if magic != _FRAME_MAGIC:
                continue
            if header.get("prime"):
                # the peer's connect-time priming frame: receive path
                # is warm, nothing to deliver
                self.primed_seen = True
                continue
            try:
                frame, src, dst = self._receive_frame_body(header)
            except Exception as e:  # noqa: BLE001
                log_error("dcn frame from %s malformed: %r", self.peer, e)
                break
            # the peer can reach coords `src`: route replies back here
            # (assignment, not setdefault — a reconnected peer's fresh
            # connection must supersede the dead one's stale route)
            with self.bridge._lock:
                self.bridge._routes[src] = self
            # bridged frames force past the local receive window: the
            # remote sender is already bounded by ITS bridge send
            # window, and dropping a delivered frame here would lose it
            # silently mid-protocol (the wire has no NACK)
            rc = fabric.send(
                frame, dst, src, _local_only=True, ignore_eovercrowded=True
            )
            if rc:
                log_error("dcn frame for unknown local coords %s dropped", (dst,))
        self.close()

    def close(self):
        if self.closed:
            return
        self.closed = True
        try:
            self.conn.close()
        except OSError:
            pass
        self.bridge._drop_conn(self)


class DcnBridge:
    """Per-process singleton: listener + outbound connections + routes."""

    def __init__(self):
        self._routes: Dict[Tuple, _BridgeConn] = {}
        self._remote_servers: Dict[Tuple, _BridgeConn] = {}
        self._conns: List[_BridgeConn] = []
        self._lock = threading.Lock()
        self._listener: Optional[_pysocket.socket] = None
        self._uds_listener: Optional[_pysocket.socket] = None
        self._uds_path: Optional[str] = None
        self._uds_dir: Optional[str] = None
        self._ssl_context = None
        self.port = 0

    # ---- routing (used by IciFabric.send) ----------------------------------
    def route(self, coords) -> Optional[_BridgeConn]:
        # check each table independently: a DEAD learned route must not
        # shadow a live advertised one (and vice versa); drop corpses.
        # _lock guards both tables — accept/reader threads insert while
        # the naming service iterates.
        with self._lock:
            for table in (self._routes, self._remote_servers):
                conn = table.get(coords)
                if conn is None:
                    continue
                if conn.closed:
                    table.pop(coords, None)
                    continue
                return conn
        return None

    def remote_server_coords(self) -> List[Tuple]:
        with self._lock:
            items = list(self._remote_servers.items())
        return sorted((c for c, conn in items if not conn.closed), key=str)

    def _drop_conn(self, conn: _BridgeConn):
        with self._lock:
            if conn in self._conns:
                self._conns.remove(conn)

    # ---- server side --------------------------------------------------------
    def listen(self, port: int = 0, host: str = "0.0.0.0",
               ssl_context=None) -> int:
        """Start accepting bridge connections; returns the bound port.
        ssl_context (an ``ssl.SSLContext`` from
        transport/ssl_helper.make_server_context) encrypts every bridge
        link — the cross-HOST leg is the one that actually crosses
        untrusted networks (reference: ssl on the RDMA bootstrap's TCP
        side channel would be the analog)."""
        if self._listener is not None:
            return self.port
        ls = _pysocket.socket()
        ls.setsockopt(_pysocket.SOL_SOCKET, _pysocket.SO_REUSEADDR, 1)
        ls.bind((host, port))
        ls.listen(16)
        self._listener = ls
        self._ssl_context = ssl_context
        self.port = ls.getsockname()[1]
        threading.Thread(target=self._accept_loop, daemon=True).start()
        # same-host fast path: a UDS listener alongside TCP, advertised
        # in the hello.  Loopback TCP moves ~2.4 GB/s on this class of
        # host where UDS moves ~7.6 GB/s (one less protocol stack), so
        # a same-host peer upgrades its bridge to the UDS path after
        # the TCP handshake.  Skipped under TLS (the TCP link is the
        # authenticated one; same-host traffic needs no wire crypto,
        # but silently downgrading crypto would surprise operators).
        if ssl_context is None:
            import os as _os
            import tempfile as _tmp

            udir = None
            try:
                # private directory (mkdtemp = 0700) + 0600 socket file,
                # both set BEFORE the path is advertised in the hello:
                # a world-writable /tmp socket would let any local user
                # connect to (or pre-create/squat) the bridge endpoint
                udir = _tmp.mkdtemp(prefix=f"dcnbridge-{_os.getpid()}-")
                upath = _os.path.join(udir, "bridge.sock")
                uls = _pysocket.socket(_pysocket.AF_UNIX)
                uls.bind(upath)
                _os.chmod(upath, 0o600)
                uls.listen(16)
                self._uds_listener = uls
                self._uds_path = upath
                self._uds_dir = udir
                threading.Thread(
                    target=self._accept_loop_uds, daemon=True
                ).start()
            except OSError as e:  # no UDS support: TCP-only is fine
                log_error("DCN UDS listener unavailable: %r", e)
                if udir is not None:  # don't orphan the private dir
                    import shutil as _shutil

                    _shutil.rmtree(udir, ignore_errors=True)
        log_info("DCN bridge listening on %s:%d%s", host, self.port,
                 " (TLS)" if ssl_context else "")
        _spawn_warmup()
        return self.port

    def _accept_loop(self):
        while self._listener is not None:
            try:
                conn, addr = self._listener.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(conn, f"{addr[0]}:{addr[1]}"),
                daemon=True,
            ).start()

    def _accept_loop_uds(self):
        while self._uds_listener is not None:
            try:
                conn, _ = self._uds_listener.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(conn, f"uds:{self._uds_path}"),
                daemon=True,
            ).start()

    def _serve_conn(self, conn: _pysocket.socket, peer: str):
        from incubator_brpc_tpu.parallel.ici import get_fabric

        if self._ssl_context is not None:
            from incubator_brpc_tpu.transport.ssl_helper import (
                wrap_server_side,
            )

            conn = wrap_server_side(
                conn, self._ssl_context, 5.0, peer, log_error
            )
            if conn is None:
                return
        msg = _read_message(conn)
        if msg is None or msg[0] != _HELLO_MAGIC:
            conn.close()
            return
        bc = _BridgeConn(self, conn, peer)
        with self._lock:
            self._conns.append(bc)
            # the peer's advertised servers are reachable through it
            # (newest connection wins: reconnects supersede dead routes)
            for raw in msg[1].get("server_coords", ()):
                c = _coords_from_wire(raw, server=True)
                if c is not None:
                    self._remote_servers[c] = bc
        self._send_hello(bc, get_fabric())
        bc.send_prime()  # warm the peer's receive path pre-traffic
        bc.reader_loop()

    # ---- client side --------------------------------------------------------
    def connect(self, host: str, port: int, timeout_s: float = 5.0,
                ssl_context=None, server_hostname: str = "") -> List[Tuple]:
        """Dial a remote bridge; returns its advertised server coords.
        ssl_context (from transport/ssl_helper.make_client_context)
        encrypts the link; server_hostname feeds SNI/verification."""
        from incubator_brpc_tpu.parallel.ici import get_fabric

        conn = _pysocket.create_connection((host, port), timeout=timeout_s)
        conn.settimeout(timeout_s)
        if ssl_context is not None:
            conn = ssl_context.wrap_socket(
                conn, server_hostname=server_hostname or None
            )
        # handshake on the raw socket BEFORE _BridgeConn wraps a TLS
        # conn in _LockedTlsSocket: single-threaded here, and the
        # timeout_s bound stays in force (the guard manages timeouts
        # per-call and would unbound this read)
        try:
            conn.sendall(self._hello_bytes(get_fabric()))
            msg = _read_message(conn)
        except OSError:
            msg = None
        if msg is None or msg[0] != _HELLO_MAGIC:
            conn.close()
            raise ConnectionError(f"dcn handshake with {host}:{port} failed")
        conn.settimeout(None)
        # same-host upgrade: a loopback peer advertising a UDS endpoint
        # gets the bridge over AF_UNIX instead (~3x loopback-TCP
        # bandwidth: one protocol stack less per byte).  The TCP
        # connection is discarded after a successful UDS handshake;
        # any failure falls back to the TCP link just established.
        uds_path = msg[1].get("uds")
        if (
            ssl_context is None
            and isinstance(uds_path, str)
            and host in ("127.0.0.1", "localhost", "::1")
        ):
            uconn = None
            try:
                uconn = _pysocket.socket(_pysocket.AF_UNIX)
                uconn.settimeout(timeout_s)
                uconn.connect(uds_path)
                uconn.sendall(self._hello_bytes(get_fabric()))
                umsg = _read_message(uconn)
                if umsg is not None and umsg[0] == _HELLO_MAGIC:
                    uconn.settimeout(None)
                    conn.close()
                    conn = uconn
                    uconn = None  # ownership moved: don't close below
                    msg = umsg
                    port_label = f"uds:{uds_path}"
                else:
                    port_label = f"{host}:{port}"
            except OSError:
                port_label = f"{host}:{port}"
            finally:
                if uconn is not None:
                    try:
                        uconn.close()
                    except OSError:
                        pass
        else:
            port_label = f"{host}:{port}"
        bc = _BridgeConn(self, conn, port_label)
        coords = [
            c
            for raw in msg[1].get("server_coords", ())
            if (c := _coords_from_wire(raw, server=True)) is not None
        ]
        with self._lock:
            for c in coords:
                self._remote_servers[c] = bc
            self._conns.append(bc)
        threading.Thread(target=bc.reader_loop, daemon=True).start()
        _spawn_warmup()
        bc.send_prime()  # warm the acceptor's receive path pre-traffic
        return coords

    def _hello_bytes(self, fabric) -> bytes:
        body = {
            "role": "fabric",
            "server_coords": [
                _coords_to_wire(c) for c in fabric.local_server_coords()
            ],
        }
        if self._uds_path is not None:
            # same-host peers may upgrade to this UDS endpoint (~3x the
            # loopback-TCP bandwidth); unknown keys are ignored by old
            # peers, so the wire stays version-compatible
            body["uds"] = self._uds_path
        header = json.dumps(body).encode()
        return _HELLO_MAGIC + struct.pack(">I", len(header)) + header

    def _send_hello(self, bc: _BridgeConn, fabric):
        with bc._send_lock:
            bc.conn.sendall(self._hello_bytes(fabric))

    def close(self):
        ls, self._listener = self._listener, None
        if ls is not None:
            try:
                ls.close()
            except OSError:
                pass
        uls, self._uds_listener = self._uds_listener, None
        if uls is not None:
            try:
                uls.close()
            except OSError:
                pass
        if self._uds_path is not None:
            import os as _os

            try:
                _os.unlink(self._uds_path)
            except OSError:
                pass
            self._uds_path = None
        if getattr(self, "_uds_dir", None) is not None:
            import os as _os

            try:
                _os.rmdir(self._uds_dir)
            except OSError:
                pass
            self._uds_dir = None
        with self._lock:
            conns, self._conns = list(self._conns), []
        for c in conns:
            c.close()
        with self._lock:
            self._routes.clear()
            self._remote_servers.clear()


_bridge: Optional[DcnBridge] = None
_bridge_lock = threading.Lock()


def get_bridge() -> DcnBridge:
    global _bridge
    if _bridge is None:
        with _bridge_lock:
            if _bridge is None:
                _bridge = DcnBridge()
    return _bridge


def listen_dcn(port: int = 0, host: str = "0.0.0.0", ssl_context=None) -> int:
    return get_bridge().listen(port, host, ssl_context=ssl_context)


def connect_dcn(
    host: str, port: int, timeout_s: float = 5.0, ssl_context=None,
    server_hostname: str = "",
) -> List[Tuple]:
    return get_bridge().connect(
        host, port, timeout_s, ssl_context=ssl_context,
        server_hostname=server_hostname,
    )
