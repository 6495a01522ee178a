"""The HBM cache's slab layout against a plain reference.

The reference is a host OrderedDict of the values in recency order,
with the store's byte semantics.  The store's budget counts the HBM it
holds — slab pages and whole-array entries — which a reference of
values cannot predict, so after each operation the reference takes the
store's evictions, checking that they were its least recently used
keys.  Seeded random sequences of SET, GET, DEL, fused and per-key
multi-SET, multi-GET and FLUSHALL, with mixed value lengths and a
budget small enough to evict, must give the same answers and the same
``rpc_cache_*`` counters, with the HBM held never over the budget — on
the store directly and through the redis front over the ICI fabric.
Beside them: the in-place row write, page reuse, pages given back as
values move between size classes, typed arrays kept whole, the fused
DMSET wire form, a GET reply that moves by reference, and the store
stamps on the server span.
"""

from collections import OrderedDict

import jax.numpy as jnp
import numpy as np
import pytest

from incubator_brpc_tpu.cache import CacheChannel, HBMCacheService, HBMCacheStore
from incubator_brpc_tpu.cache import store as cache_store
from incubator_brpc_tpu.cache.channel import CacheError, dmset_fused_command
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.observability import span as span_mod
from incubator_brpc_tpu.protocols import redis as R
from incubator_brpc_tpu.server.server import Server, ServerOptions
from incubator_brpc_tpu.utils.iobuf import DeviceRef

# ICI coords are process-global: this suite owns slices 90+
_slices = [90]


def _slice():
    _slices[0] += 1
    return _slices[0]


COUNTERS = ("hits", "misses", "evictions", "hbm_bytes")


def _counters():
    return {k: int(getattr(cache_store, "cache_" + k).get_value())
            for k in COUNTERS}


def _delta(before):
    now = _counters()
    return {k: now[k] - before[k] for k in COUNTERS}


def _bytes(v):
    if v is None or isinstance(v, bytes):
        return v
    return bytes(DeviceRef(v).view())


class RefStore:
    """The plain reference: values as host bytes in an OrderedDict."""

    def __init__(self, budget):
        self.budget = budget
        self.d = OrderedDict()
        self.c = dict.fromkeys(COUNTERS, 0)

    @property
    def used(self):
        return sum(map(len, self.d.values()))

    def set(self, k, v):
        if len(v) > self.budget:
            return False
        self.d.pop(k, None)
        self.d[k] = v
        return True

    def get(self, k):
        if k not in self.d:
            self.c["misses"] += 1
            return None
        self.d.move_to_end(k)
        self.c["hits"] += 1
        return self.d[k]

    def delete(self, k):
        return self.d.pop(k, None) is not None

    def set_stacked(self, keys, rows, lengths):
        stored = 0
        for k, row, n in zip(keys, rows, lengths):
            if 0 < n <= min(len(row), self.budget):
                self.set(k, bytes(row[:n]))
                stored += 1
        return stored

    def flush(self):
        n = len(self.d)
        self.d.clear()
        return n

    def sync(self, keys):
        """Take the store's evictions: ``keys``, the store's keys oldest
        first, must be the newest of the reference's."""
        ref = list(self.d)
        m = len(ref) - len(keys)
        assert m >= 0 and ref[m:] == keys, (ref, keys)
        for k in ref[:m]:
            del self.d[k]
        self.c["evictions"] += m

    def check(self, before, keys, held, budget, batch=False):
        """The store's counters and HBM after an operation.  A batch may
        evict a key it wrote itself and write it again: the store counts
        that eviction, the reference sees none."""
        self.sync(keys)
        d = _delta(before)
        if batch:
            assert d["evictions"] >= self.c["evictions"]
            self.c["evictions"] = d["evictions"]
        self.c["hbm_bytes"] = self.used
        assert d == self.c
        assert held <= budget


def _ledger():
    from incubator_brpc_tpu.observability import profiling

    return sum(profiling.hbm_account(t).live_bytes()
               for t in ("cache.slab", "cache.values"))


LENGTHS = (1, 7, 63, 64, 65, 100, 1000, 1500)


def _ops(seed, n, keys=12):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        r = rng.random()
        k = b"k%d" % rng.integers(keys)
        if r < 0.3:
            yield "set", k, rng.bytes(int(rng.choice(LENGTHS)))
        elif r < 0.6:
            yield "get", k, None
        elif r < 0.7:
            yield "del", k, None
        elif r < 0.8:
            b = int(rng.integers(1, 6))
            width = int(rng.choice(LENGTHS))
            ks = [b"k%d" % x for x in rng.integers(keys, size=b)]
            rows = rng.integers(0, 256, (b, width), dtype=np.uint8)
            lens = rng.integers(0, width + 1, b)  # 0: skipped
            yield "fused", ks, (rows, lens)
        elif r < 0.87:
            yield "pairs", [(b"k%d" % rng.integers(keys),
                             rng.bytes(int(rng.choice(LENGTHS))))
                            for _ in range(int(rng.integers(1, 4)))], None
        elif r < 0.98:
            yield "mget", [b"k%d" % x for x in
                           rng.integers(keys, size=int(rng.integers(1, 6)))], None
        else:
            yield "flush", None, None


def _budget(seed):
    # 5000: rows of 64 and 128 B (a page is 1/16 of the budget), the
    # rest whole entries; 40000: every length a row
    return 5000 if seed % 4 in (1, 2) else 40000


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_store_matches_the_reference(seed):
    budget = _budget(seed)
    st, ref = HBMCacheStore(hbm_budget_bytes=budget), RefStore(budget)
    before, ledger0 = _counters(), _ledger()
    for op, a, b in _ops(seed, 300):
        if op == "set":
            assert st.set(a, b) == ref.set(a, b)
        elif op == "get":
            assert _bytes(st.get(a)) == ref.get(a)
        elif op == "del":
            assert st.delete(a) == ref.delete(a)
        elif op == "fused":
            rows, lens = b
            dev = jnp.asarray(rows) if seed % 2 else rows
            assert st.set_stacked(a, dev, lens) == ref.set_stacked(a, rows, lens)
        elif op == "pairs":
            for k, v in a:
                assert st.set(k, v) == ref.set(k, v)
                ref.check(before, st.keys(), st.hbm_held, budget)
        elif op == "mget":
            values, stacked = st.get_many(a)
            assert [_bytes(v) for v in values] == [ref.get(k) for k in a]
            if stacked is not None:
                hits = [ref.d[k] for k in a if k in ref.d]
                assert len({len(h) for h in hits}) == 1
                got = np.asarray(stacked)[:len(hits)]
                assert [bytes(r) for r in got] == hits
        else:
            assert st.flush() == ref.flush()
        ref.check(before, st.keys(), st.hbm_held, budget, batch=op == "fused")
        assert st.hbm_used == ref.used
        assert _ledger() - ledger0 == st.hbm_held
    if budget == 5000:
        assert ref.c["evictions"] > 0
    st.flush()
    assert _ledger() == ledger0


def _ici_server(budget, device=None):
    s = _slice()
    svc = HBMCacheService(hbm_budget_bytes=budget, device=device)
    srv = Server(ServerOptions(redis_service=svc))
    assert srv.start_ici(s, 1, device=device) == 0
    ch = Channel(ChannelOptions(protocol="redis", timeout_ms=30000,
                                ici_device=device))
    assert ch.init(f"ici://slice{s}/chip1") == 0
    srv.test_addr = f"ici://slice{s}/chip1"
    return srv, svc, ch


def _call(ch, *cmd):
    req = R.RedisRequest()
    req.add_command(*cmd)
    resp = R.RedisResponse()
    ctrl = Controller()
    ch.call_method(R.redis_method_spec(), ctrl, req, resp)
    return None if ctrl.failed() else resp.reply(0)


def _reply_bytes(r):
    arr = r.device_array()
    return _bytes(arr) if arr is not None else r.bytes_value()


@pytest.mark.parametrize("seed", [5, 6])
def test_redis_front_over_ici_matches_the_reference(seed):
    """The same sequences as RESP commands over the fabric: GET, SET,
    DEL, per-key and fused DMSET, DMGET, FLUSHALL."""
    budget = _budget(seed)
    srv, svc, ch = _ici_server(budget)
    ref = RefStore(budget)
    before = _counters()
    try:
        for op, a, b in _ops(seed, 120):
            if op == "set":
                r = _call(ch, "SET", a, b)
                assert (r is not None) == ref.set(a, b)
            elif op == "get":
                r = _call(ch, "GET", a)
                want = ref.get(a)
                assert (None if r.is_nil() else _reply_bytes(r)) == want
            elif op == "del":
                assert _call(ch, "DEL", a).value == int(ref.delete(a))
            elif op == "fused":
                rows, lens = b
                r = _call(ch, *dmset_fused_command(a, jnp.asarray(rows), lens))
                assert r.value == ref.set_stacked(a, rows, lens)
            elif op == "pairs":
                flat = [x for kv in a for x in kv]
                r = _call(ch, "DMSET", *flat)
                assert r.value == sum(ref.set(k, v) for k, v in a)
            elif op == "mget":
                fused, lengths, payload = _call(ch, "DMGET", *a).value
                want = [ref.get(k) for k in a]
                assert [x.value for x in lengths.value] == [
                    -1 if w is None else len(w) for w in want]
                hits = [w for w in want if w is not None]
                if fused.value == 1:
                    rows = np.asarray(payload.device_array())
                    assert [bytes(r[:len(h)]) for r, h in zip(rows, hits)] == hits
                else:
                    got = [None if x.is_nil() else _reply_bytes(x)
                           for x in payload.value]
                    assert got == want
            else:
                assert _call(ch, "FLUSHALL").value == "OK"
                ref.flush()
            ref.check(before, svc.store.keys(), svc.store.hbm_held, budget,
                      batch=op in ("fused", "pairs"))
    finally:
        ch.close()
        srv.stop()
        svc.store.flush()


def test_eviction_frees_rows_and_pages_are_reused():
    # a 64 KiB budget: pages of 4 KiB, four 1 KiB rows each
    st = HBMCacheStore(hbm_budget_bytes=64 << 10)
    pages0 = int(cache_store.slab_pages.get_value())
    for i in range(64):
        assert st.set(b"a%d" % i, bytes([i]) * 1000)
    # sixteen pages fill the budget, and no value was evicted
    slab = st.slab_bytes
    assert slab == st.hbm_held == st.budget and len(st) == 64
    ev0 = int(cache_store.cache_evictions.get_value())
    # four more 1000 B values: each evicts the oldest and takes its row
    for i in range(4):
        assert st.set(b"b%d" % i, bytes([9]) * 1000)
    assert int(cache_store.cache_evictions.get_value()) - ev0 == 4
    assert st.keys()[:2] == [b"a4", b"a5"]
    assert st.slab_bytes == slab
    # DEL frees rows; new values take them, and no page is added
    for k in st.keys()[4:]:
        assert st.delete(k)
    for i in range(3):
        assert st.set(b"c%d" % i, bytes([i + 1]) * 900)
    assert st.slab_bytes == slab
    assert int(cache_store.slab_pages.get_value()) - pages0 == len(
        [p for c in st._classes.values() for p in c.pages])
    for i in range(3):
        assert _bytes(st.get(b"c%d" % i)) == bytes([i + 1]) * 900
    st.flush()
    assert int(cache_store.slab_pages.get_value()) == pages0
    assert st.slab_bytes == 0


def test_values_moving_across_size_classes_stay_within_the_budget():
    """Waves of values of one length after another under a 16 KiB
    budget (pages of 1 KiB): each class takes pages the one before gave
    back, the HBM held never passes the budget, evictions take the
    oldest keys, and the newest values read back exact."""
    budget = 16 << 10
    st = HBMCacheStore(hbm_budget_bytes=budget)
    ledger0 = _ledger()
    pages0 = int(cache_store.slab_pages.get_value())
    order, dropped = [], 0
    for wave, n in enumerate((1000, 60, 500, 1000, 200, 64, 3000, 1000)):
        for i in range(24):
            key = b"w%d-%d" % (wave, i)
            assert st.set(key, bytes([wave, i]) * (n // 2) + b"\x07" * (n % 2))
            order.append(key)
            assert st.hbm_held <= budget
            assert _ledger() - ledger0 == st.hbm_held
            # the keys left are the newest ones, in order
            keys = st.keys()
            assert keys == order[len(order) - len(keys):]
        pages = int(cache_store.slab_pages.get_value()) - pages0
        dropped = max(dropped, len(order) - len(st.keys()))
        for key in st.keys()[-8:]:
            w, i = map(int, key[1:].split(b"-"))
            assert _bytes(st.get(key)) == bytes([w, i]) * (n // 2) + b"\x07" * (n % 2)
    assert dropped > 0 and pages * 1024 <= budget
    # every class of the last wave but its own gave its pages back
    assert {w for w, c in st._classes.items() if c.pages} == {1024}
    st.flush()
    assert _ledger() == ledger0


def test_get_many_returns_whole_arrays_as_stored():
    st = HBMCacheStore(hbm_budget_bytes=1 << 20)
    vals = [jnp.full((4,), i, jnp.float32) for i in range(3)]
    for i, v in enumerate(vals):
        assert st.set(b"t%d" % i, v)
    values, stacked = st.get_many([b"t0", b"none", b"t1", b"t2"])
    assert stacked is not None and tuple(stacked.shape) == (4, 4)
    assert values[0] is vals[0] and values[2] is vals[1]
    assert values[3] is vals[2] and values[1] is None
    st.flush()


def test_a_write_leaves_the_pages_other_rows_bit_equal():
    st = HBMCacheStore(hbm_budget_bytes=1 << 20)
    rng = np.random.default_rng(0)
    vals = {b"r%d" % i: rng.bytes(200) for i in range(40)}
    for k, v in vals.items():
        st.set(k, v)
    (cls,) = st._classes.values()
    before = np.asarray(cls.pages[0]).copy()
    assert st.set(b"r17", b"\xff" * 180)  # its class: back into its row
    after = np.asarray(cls.pages[0])
    changed = np.flatnonzero((before != after).any(axis=1))
    assert len(changed) == 1
    for k, v in vals.items():
        if k != b"r17":
            assert _bytes(st.get(k)) == v
    assert _bytes(st.get(b"r17")) == b"\xff" * 180
    st.flush()


def test_row_writes_and_programs_are_counted():
    st = HBMCacheStore(hbm_budget_bytes=1 << 20)
    w0 = int(cache_store.slab_writes.get_value())
    p0 = int(cache_store.slab_write_programs.get_value())
    r0 = int(cache_store.slab_rows.get_value())
    rows = np.arange(10 * 100, dtype=np.uint8).reshape(10, 100)
    assert st.set_stacked([b"f%d" % i for i in range(10)], jnp.asarray(rows),
                          [100] * 10) == 10
    assert st.set(b"one", b"x" * 100)
    assert int(cache_store.slab_writes.get_value()) - w0 == 11
    # one scatter for the ten rows (one page), one write for the SET
    assert int(cache_store.slab_write_programs.get_value()) - p0 == 2
    assert int(cache_store.slab_rows.get_value()) - r0 == 11
    assert st.delete(b"one")
    assert int(cache_store.slab_rows.get_value()) - r0 == 10
    st.flush()
    assert int(cache_store.slab_rows.get_value()) == r0


@pytest.mark.parametrize("value", [
    jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
    jnp.ones((16,), jnp.bfloat16),
    jnp.zeros((4, 8), jnp.uint8),
], ids=["f32", "bf16", "uint8_2d"])
def test_typed_and_shaped_arrays_stay_whole(value):
    st = HBMCacheStore(hbm_budget_bytes=1 << 20)
    assert st.set(b"t", value)
    assert st.get(b"t") is value
    assert st.set(b"r", DeviceRef(value))
    assert st.get(b"r") is value
    assert st.slab_bytes == 0
    assert st.hbm_used == 2 * int(value.nbytes)
    st.flush()


def test_values_over_a_row_stay_whole_and_exact():
    st = HBMCacheStore(hbm_budget_bytes=4 << 20)
    big = bytes(range(256)) * ((cache_store.ROW_MAX + 256) // 256)
    assert len(big) > cache_store.ROW_MAX
    assert st.set(b"big", big)
    assert st.set(b"empty", b"")
    assert st.get_host(b"big") == big
    assert st.get_host(b"empty") == b""
    assert st.slab_bytes == 0
    st.flush()


def test_fused_dmset_from_the_channel_host_and_device_rows():
    srv, svc, ch = _ici_server(1 << 20)
    ch.close()
    cc = CacheChannel(f"list://{srv.test_addr}", lb="rr")
    try:
        rows = np.arange(6 * 50, dtype=np.uint8).reshape(6, 50)
        keys = [f"fz{i}" for i in range(6)]
        assert cc.set_stacked(keys, jnp.asarray(rows), [50, 10, 50, 1, 50, 50]) == 6
        assert cc.get_host("fz1") == rows[1, :10].tobytes()
        assert cc.get_host("fz3") == rows[3, :1].tobytes()
        res = cc.get_many(["fz0", "fz2", "fz4", "nope"])
        assert res.stacked is not None
        assert res.host_bytes(1) == rows[2].tobytes() and res.row(3) is None
        assert cc.set_stacked(["h0", "h1"], rows[:2]) == 2  # host rows
        assert cc.get_host("h1") == rows[1].tobytes()
        with pytest.raises(CacheError):
            cc.set_stacked(["z"], rows[:1], [0])  # an empty value fits no row
    finally:
        cc.close()
        srv.stop()
        svc.store.flush()


def test_fused_dmset_malformed_is_an_error_reply():
    srv, svc, ch = _ici_server(1 << 20)
    try:
        rows = jnp.zeros((2, 8), jnp.uint8)
        cmd = list(dmset_fused_command([b"a", b"b"], rows, [8, 8]))
        bad_keys = cmd[:5] + [b"abc"]  # key lengths say 2 bytes
        assert _call(ch, *bad_keys) is None
        bad_rows = cmd[:3] + [jnp.zeros((3, 8), jnp.uint8)] + cmd[4:]
        assert _call(ch, *bad_rows) is None
        bad_lengths = cmd[:2] + [b"\x08\x00\x00"] + cmd[3:]  # not int32s
        assert _call(ch, *bad_lengths) is None
        assert len(svc.store) == 0
        assert _call(ch, *cmd).value == 2
    finally:
        ch.close()
        srv.stop()
        svc.store.flush()


def test_a_get_reply_moves_by_reference(monkeypatch):
    """A GET costs one device program: the row slice, handed off to
    its reply, crosses the same-chip hop without the transmit copy."""
    import jax

    from incubator_brpc_tpu.parallel.ici import IciFabric

    srv, svc, ch = _ici_server(1 << 20, device=jax.devices()[0])
    calls = []
    real = IciFabric._transmit_segment

    def counting(self, arr, dst_port, leg):
        calls.append(tuple(arr.shape))
        return real(self, arr, dst_port, leg)

    monkeypatch.setattr(IciFabric, "_transmit_segment", counting)
    try:
        assert _call(ch, "SET", b"k", b"v" * 100).value == "OK"
        r = _call(ch, "GET", b"k")
        assert _reply_bytes(r) == b"v" * 100
        assert calls == []
        # a device value the client still holds is copied by the hop
        value = jnp.full((64,), 3, jnp.uint8)
        assert _call(ch, "SET", b"d", value).value == "OK"
        assert calls == [(64,)]
        assert _bytes(svc.store.get(b"d")) == b"\x03" * 64
    finally:
        ch.close()
        srv.stop()
        svc.store.flush()


def test_store_stamps_the_server_span_only_under_a_capture():
    srv, svc, ch = _ici_server(1 << 20)
    try:
        assert _call(ch, "SET", b"k", b"v" * 64).value == "OK"
        span_mod.start_capture()
        try:
            assert _call(ch, "GET", b"k") is not None
            assert _call(ch, "SET", b"k", b"w" * 64).value == "OK"
        finally:
            cap = span_mod.stop_capture()
        servers = [s for s in cap.spans if s.kind == "server" and s.service == "redis"]
        assert {s.method for s in servers} >= {"GET", "SET"}
        for s in servers:
            assert s.callback_start_us <= s.store_start_us <= s.store_done_us
            assert s.store_done_us <= s.callback_done_us
        # outside a capture no server span exists to stamp
        fresh = span_mod.Span("server")
        assert fresh.store_start_us == fresh.store_done_us == 0
    finally:
        ch.close()
        srv.stop()
        svc.store.flush()


def test_reads_and_writes_from_many_threads_keep_every_value():
    """Writes donate their page; reads capture it under the same lock,
    so no read ever meets a donated buffer."""
    import threading

    st = HBMCacheStore(hbm_budget_bytes=1 << 20)
    for i in range(32):
        st.set(b"k%d" % i, bytes([i]) * 100)
    errs = []

    def work(t):
        rng = np.random.default_rng(t)
        try:
            for _ in range(200):
                i = int(rng.integers(32))
                if rng.random() < 0.5:
                    st.set(b"k%d" % i, bytes([i]) * 100)
                else:
                    assert _bytes(st.get(b"k%d" % i)) == bytes([i]) * 100
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)

    ths = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert errs == []
    st.flush()
