"""Protocol fronts for the HBM cache store: redis + memcache.

One `HBMCacheStore` can sit behind both protocols on the same server
(``ServerOptions.redis_service`` and ``.memcache_service``), so any
off-the-shelf redis or binary-memcached client reads the cluster cache.

Reply residency is decided PER CONNECTION: an ICI-peer socket
(``sock.ici_port``) gets the value as a DeviceRef segment — HBM to HBM
through the fabric's transmit, zero pulls — while a host transport
(TCP/DCN client) gets exact bytes through the store's manifested
``cache.host-spill`` choke point.

Redis command surface: GET/SET/DEL/EXISTS/MGET/STRLEN/FLUSHALL/DBSIZE
plus the device-batched DMGET (see `HBMCacheService.dmget`): same-length
hit groups coalesce through the store's fused gather into ONE stacked
bulk, with a lengths header the client unpacks rows from.  DMSET is the
write-side mirror — one round trip ingests a whole key range, so the
resharding coordinator's bulk COPY crosses the wire per DESTINATION,
not per key.  Its fused form (``DMSET 1 <lengths> <stacked> <key
lengths> <keys>``) mirrors DMGET's fused reply: a batch of values
crosses ICI as ONE (B, L) device segment and lands in its slab rows
through one scatter program, with no per-value parse.

Drain batches: inside a server port's drain scope (``runtime/drain.py``)
a GET, and a SET of a slab-row value (host bytes of 1 to the store's
``row_max``), from an ICI peer is deferred to the batch's close, where
the batch's deferred commands are one ``HBMCacheStore.apply_batch`` call
(one device program for the lot) and their replies leave in arrival
order.  The call applies the SETs, then the GETs; a SET that follows a
GET of its key starts a new batch, so each key's commands take effect
in arrival order.  Every other command, and every command of a host
transport, runs at once, after flushing what the batch deferred before
it.

While an rpcz capture is armed, the redis server span gets
``store_start_us``/``store_done_us`` around the store's part of each
command (docs/observability.md); a deferred command's bracket the
shared ``apply_batch`` call.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from incubator_brpc_tpu.cache.store import HBMCacheStore
from incubator_brpc_tpu.observability.span import capture_armed, current_span
from incubator_brpc_tpu.protocols.memcache import (
    OP_GET,
    STATUS_KEY_NOT_FOUND,
    STATUS_OK,
    MemcacheService,
)
from incubator_brpc_tpu.protocols.redis import (
    REPLY_STRING,
    DeferredReply,
    RedisReply,
    RedisService,
)
from incubator_brpc_tpu.runtime.drain import current_drain
from incubator_brpc_tpu.utils.iobuf import DeviceRef
from incubator_brpc_tpu.utils.logging import log_error


def _is_ici(sock) -> bool:
    return getattr(sock, "ici_port", None) is not None


class HBMCacheService(RedisService):
    """Redis front of the cache tier (connection-aware: the protocol
    routes through ``handle_conn`` so replies know their transport)."""

    def __init__(self, store: Optional[HBMCacheStore] = None, **store_kwargs):
        self.store = store if store is not None else HBMCacheStore(**store_kwargs)
        # the current connection, stashed per worker thread so command
        # methods (fixed handle() signature) can see their transport
        self._tls = threading.local()

    @property
    def _sock(self):
        return getattr(self._tls, "sock", None)

    # protocols.redis.process_request prefers this over handle()
    def handle_conn(self, command: str, args: List, sock) -> RedisReply:
        cmd = command.upper()
        # the redis server span exists only under a capture
        span = current_span() if capture_armed() else None
        scope = current_drain()
        if scope is not None:
            if self._deferrable(cmd, args, sock):
                if cmd == "SET" and self._get_pending(scope, args[0]):
                    # a batch reads after it writes: a SET after a GET of
                    # its key waits for the next batch, so every key sees
                    # its commands in arrival order
                    scope.flush()
                reply = DeferredReply()
                scope.defer(self._flush_drain, (reply, args, span))
                return reply
            # the store sees the batch's deferred commands first
            scope.flush()
        self._tls.sock = sock
        self._tls.span = span
        try:
            if cmd == "DEL":  # python keyword, same aliasing as KVRedisService
                self._stamp("store_start_us")
                n = sum(1 for k in args if self.store.delete(k))
                self._stamp("store_done_us")
                return RedisReply.integer(n)
            return self.handle(command, args)
        finally:
            self._tls.sock = self._tls.span = None

    def _deferrable(self, cmd: str, args: List, sock) -> bool:
        """A GET, or a SET of a slab row's host bytes, from an ICI peer."""
        if not (_is_ici(sock) and self.store.enabled
                and args and type(args[0]) is bytes):
            return False
        if cmd == "GET":
            return len(args) == 1
        return (cmd == "SET" and len(args) == 2 and type(args[1]) is bytes
                and 0 < len(args[1]) <= self.store.row_max)

    def _get_pending(self, scope, key: bytes) -> bool:
        return any(len(a) == 1 and a[0] == key
                   for _, a, _ in scope.pending.get(self._flush_drain, ()))

    def _flush_drain(self, members: List) -> None:
        """A drain batch's deferred GETs and SETs: one store call, then
        each reply, in arrival order.  If the store raises, every member
        gets an error reply."""
        sets = [(a[0], a[1]) for _, a, _ in members if len(a) == 2]
        gets = [a[0] for _, a, _ in members if len(a) == 1]
        start = time.time_ns() // 1000
        try:
            stored, values = self.store.apply_batch(sets, gets)
        except Exception as e:  # noqa: BLE001 — answered below
            log_error("cache drain batch of %d failed: %r", len(members), e)
            replies = [RedisReply.error(f"ERR internal: {e}")] * len(members)
        else:
            done = time.time_ns() // 1000
            stored, values = iter(stored), iter(values)
            replies = [self._set_reply(next(stored)) if len(a) == 2
                       else self._get_reply(next(values))
                       for _, a, _ in members]
            for _, _, span in members:
                if span is not None:
                    span.store_start_us, span.store_done_us = start, done
        for (reply, _, _), r in zip(members, replies):
            try:
                reply.send(r)
            except Exception as e:  # noqa: BLE001 — the others still go
                log_error("cache drain reply failed: %r", e)

    def _stamp(self, field: str) -> None:
        span = getattr(self._tls, "span", None)
        if span is not None:
            setattr(span, field, time.time_ns() // 1000)

    def _value_reply(self, key: bytes) -> RedisReply:
        self._stamp("store_start_us")
        if _is_ici(self._sock):
            v = self.store.get(key)
        else:
            v = self.store.get_host(key)
        self._stamp("store_done_us")
        return self._get_reply(v)

    @staticmethod
    def _get_reply(v) -> RedisReply:
        if v is None:
            return RedisReply.nil()
        if isinstance(v, bytes):
            return RedisReply.bulk(v)
        return RedisReply(REPLY_STRING, v)  # device, to an ICI peer

    @staticmethod
    def _set_reply(ok: bool) -> RedisReply:
        if not ok:
            return RedisReply.error("ERR value exceeds cache HBM budget")
        return RedisReply.status("OK")

    # ---- commands (lower-case name == wire name) ---------------------------
    def get(self, key):
        return self._value_reply(key)

    def set(self, key, value):
        if value is None:
            return RedisReply.error("ERR protocol error: SET value missing")
        self._stamp("store_start_us")
        ok = self.store.set(key, value)
        self._stamp("store_done_us")
        return self._set_reply(ok)

    def exists(self, key):
        return 1 if key in self.store else 0

    def strlen(self, key):
        v = self.store.get(key)
        if v is None:
            return 0
        return len(v) if isinstance(v, bytes) else int(v.nbytes)

    def mget(self, *keys):
        # standard redis MGET: per-key bulks, no fusion (redis-cli
        # compatible); the fused device batch is DMGET
        return RedisReply.array([self._value_reply(k) for k in keys])

    def dmget(self, *keys):
        """Device multi-GET → [fused, lengths, payload]:

        fused=1: every hit shares one length; ``payload`` is ONE
        stacked (bucket, L) device bulk — hit i is row i in hit order
        (misses carry length -1 and consume no row).
        fused=0: ``payload`` is a per-key array of bulks like MGET."""
        if not keys:
            return RedisReply.error("ERR wrong number of arguments for 'dmget'")
        ici = _is_ici(self._sock)
        self._stamp("store_start_us")
        lengths, stacked, values = self.store.lookup_many(keys, fuse=ici)
        self._stamp("store_done_us")
        lengths_r = RedisReply.array([RedisReply.integer(n) for n in lengths])
        if stacked is not None:
            return RedisReply.array([
                RedisReply.integer(1),
                lengths_r,
                RedisReply(REPLY_STRING, stacked),
            ])
        per_key = []
        for v in values:
            if v is None:
                per_key.append(RedisReply.nil())
            elif isinstance(v, bytes):
                per_key.append(RedisReply.bulk(v))
            elif ici:
                per_key.append(RedisReply(REPLY_STRING, v))
            else:
                per_key.append(RedisReply.bulk(self.store.spill(v)))
        return RedisReply.array([
            RedisReply.integer(0), lengths_r, RedisReply.array(per_key),
        ])

    def dmset(self, *args):
        """Device multi-SET → integer count of values stored.

        ``DMSET k1 v1 k2 v2 ...``: the ingest counterpart of DMGET — a
        resharding COPY range (or any batched writer) lands on a
        replica as ONE round trip instead of one SET per key, the
        collective bulk-move leg of the Pallas data plane.  Byte values
        land as the fused form's do, one scatter per slab page.

        ``DMSET 1 <lengths> <stacked> <key lengths> <keys>`` (fused;
        never a pair list, whose arity is even): ``stacked`` is one
        (B, L) uint8 bulk — a device segment over ICI, B × L host bytes
        otherwise — whose row i holds key i's value in its first
        ``lengths[i]`` bytes; ``lengths`` and ``key lengths`` are B
        little-endian int32 each, ``keys`` the B keys back to back.
        The rows land through one scatter program per slab page.

        Values over the HBM budget are skipped (count < pairs tells the
        client which path to retry)."""
        if len(args) == 5 and args[0] == b"1":
            return self._dmset_fused(*args[1:])
        if not args or len(args) % 2:
            return RedisReply.error(
                "ERR wrong number of arguments for 'dmset'"
            )
        keys, values = args[0::2], args[1::2]
        self._stamp("store_start_us")
        if all(type(v) is bytes and 0 < len(v) <= self.store.row_max
               for v in values):
            # byte values: one scatter program per slab page touched
            rows = np.zeros((len(values), max(map(len, values))), np.uint8)
            for row, v in zip(rows, values):
                row[:len(v)] = np.frombuffer(v, np.uint8)
            stored = self.store.set_stacked(keys, rows, list(map(len, values)))
        else:
            stored = sum(1 for k, v in zip(keys, values) if self.store.set(k, v))
        self._stamp("store_done_us")
        return RedisReply.integer(stored)

    def _dmset_fused(self, lengths, stacked, key_lengths, keys):
        if not all(isinstance(a, bytes) for a in (lengths, key_lengths, keys)):
            return RedisReply.error("ERR DMSET lengths and keys must be host bulks")
        if len(lengths) % 4 or len(key_lengths) % 4:
            return RedisReply.error("ERR DMSET lengths are not int32s")
        lens = np.frombuffer(lengths, "<i4")
        klens = np.frombuffer(key_lengths, "<i4")
        ends = np.cumsum(klens, dtype=np.int64)
        if len(klens) != len(lens) or (klens < 0).any() or (
                len(ends) and ends[-1] != len(keys)):
            return RedisReply.error("ERR DMSET key lengths do not match the keys")
        starts = (ends - klens).tolist()
        key_list = [keys[a:b] for a, b in zip(starts, ends.tolist())]
        if isinstance(stacked, DeviceRef):
            whole = stacked.whole_array()
            stacked = whole if whole is not None else bytes(stacked.view())
        if isinstance(stacked, bytes):
            if len(lens) == 0 or len(stacked) % len(lens):
                return RedisReply.error("ERR DMSET stacked bulk is not B rows")
            stacked = np.frombuffer(stacked, np.uint8).reshape(len(lens), -1)
        if stacked.ndim != 2 or stacked.shape[0] != len(lens) or (
                stacked.dtype != np.uint8):
            return RedisReply.error("ERR DMSET stacked bulk is not (B, L) uint8")
        self._stamp("store_start_us")
        stored = self.store.set_stacked(key_list, stacked, lens)
        self._stamp("store_done_us")
        return RedisReply.integer(stored)

    def keys(self, *args):
        """Key census for the re-sharding coordinator (argument-free —
        no glob matching; migrations enumerate everything)."""
        return RedisReply.array(
            [RedisReply.bulk(k) for k in self.store.keys()]
        )

    def flushall(self, *args):
        self.store.flush()
        return RedisReply.status("OK")

    def dbsize(self):
        return len(self.store)


class HBMCacheMemcacheService(MemcacheService):
    """Memcache front over the SAME store: GET serves the device array
    to ICI peers (the binary framing ships it as the value region),
    spills to host bytes for everyone else; SET/DELETE/FLUSH hit the
    shared store so both protocols see one cache."""

    def __init__(self, store: Optional[HBMCacheStore] = None, **store_kwargs):
        super().__init__()
        self.store = store if store is not None else HBMCacheStore(**store_kwargs)

    def handle_op(self, op, sock):
        import struct

        scope = current_drain()
        if scope is not None:  # the redis front's deferred commands first
            scope.flush()
        code = op.opcode
        if code == OP_GET:
            if _is_ici(sock):
                v = self.store.get(op.key)
            else:
                v = self.store.get_host(op.key)
            if v is None:
                return STATUS_KEY_NOT_FOUND, b"", b"Not found", 0
            return STATUS_OK, struct.pack(">I", 0), v, 0
        if code == 0x01:  # OP_SET
            value = op.value
            if not isinstance(value, (bytes, DeviceRef)):
                value = bytes(value)
            if not self.store.set(op.key, value):
                return 0x0005, b"", b"", 0  # ITEM_NOT_STORED: over budget
            return STATUS_OK, b"", b"", 0
        if code == 0x04:  # OP_DELETE
            ok = self.store.delete(op.key)
            return (STATUS_OK if ok else STATUS_KEY_NOT_FOUND), b"", b"", 0
        if code == 0x08:  # OP_FLUSH
            self.store.flush()
            return STATUS_OK, b"", b"", 0
        return super().handle_op(op, sock)
