"""Drain batches of the HBM cache: a completion-queue drain's GETs and
SETs of slab rows as one store call (``HBMCacheStore.apply_batch``) and
one device program a size class (``cache_slab_scatter_gather``).

The reference is a host dict.  On the store, a batch is its SETs in
order, then its GETs.  Over the redis front, one pipelined request is
one frame, so its commands land in one drain batch; each key's commands
take effect in arrival order.  Beside them: batch sizes around the
program's buckets, rows freed and taken again inside a batch, commands
that flush the batch first, a raising program, a TCP client, the
counters under concurrency, the traced variants, and the XLA module
name the benchmark's slab reader matches.
"""

import importlib.util
import os
import re
import sys
import threading

import jax
import numpy as np
import pytest

from incubator_brpc_tpu.cache import HBMCacheService, HBMCacheStore
from incubator_brpc_tpu.cache import store as cache_store
from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
from incubator_brpc_tpu.client.controller import Controller
from incubator_brpc_tpu.protocols import redis as R
from incubator_brpc_tpu.server.server import Server, ServerOptions
from incubator_brpc_tpu.utils.iobuf import DeviceRef

# ICI coords are process-global: this suite owns slices 300+
_slices = [300]

# a 1 MiB budget: pages of 64 KiB, so 1 KiB rows fill a page at 64
BUDGET = 1 << 20


def _slice():
    _slices[0] += 1
    return _slices[0]


def _bytes(v):
    if v is None or isinstance(v, bytes):
        return v
    return bytes(DeviceRef(v).view())


def _batch_counters():
    return (int(cache_store.slab_batch_programs.get_value()),
            int(cache_store.slab_batch_ops.get_value()))


def _loaded_store(n=200, length=1000, budget=BUDGET):
    """A store whose 1 KiB class holds ``n`` keys over several pages,
    and the reference of them."""
    st = HBMCacheStore(hbm_budget_bytes=budget)
    rng = np.random.default_rng(n)
    ref = {b"k%d" % i: rng.bytes(length) for i in range(n)}
    for k, v in ref.items():
        assert st.set(k, v)
    return st, ref


def _random_batch(rng, keys, n, lengths=(1000,)):
    sets, gets = [], []
    for _ in range(n):
        k = keys[int(rng.integers(len(keys)))]
        if rng.random() < 0.5:
            sets.append((k, rng.bytes(int(rng.choice(lengths)))))
        else:
            gets.append(k)
    return sets, gets


def _apply_reference(ref, sets, gets):
    for k, v in sets:
        ref[k] = v
    return [ref.get(k) for k in gets]


def _check_batch(st, ref, sets, gets):
    stored, values = st.apply_batch(sets, gets)
    want = _apply_reference(ref, sets, gets)
    assert stored == [True] * len(sets)
    assert [_bytes(v) for v in values] == want


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_a_batch_is_its_writes_then_its_reads(seed):
    """Mixed lengths (several size classes and read lengths), misses,
    whole-array entries and keys given twice, against the dict."""
    st, ref = _loaded_store()
    big = bytes(range(256)) * 512  # wider than the budget's widest row
    assert st.set(b"big", big)
    ref[b"big"] = big
    rng = np.random.default_rng(seed)
    keys = list(ref) + [b"miss%d" % i for i in range(5)]
    for _ in range(40):
        sets, gets = _random_batch(rng, keys, int(rng.integers(1, 24)),
                                   lengths=(1, 100, 700, 1000))
        sets = [(k, v) for k, v in sets if k != b"big"]
        _check_batch(st, ref, sets, gets)
    for k, v in ref.items():
        assert _bytes(st.get(k)) == v
    st.flush()


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 9, 63, 64, 65])
def test_batch_sizes_around_the_buckets(n):
    st, ref = _loaded_store()
    rng = np.random.default_rng(n)
    p0, o0 = _batch_counters()
    sets, gets = _random_batch(rng, list(ref), n)
    _check_batch(st, ref, sets, gets)
    programs, ops = (a - b for a, b in zip(_batch_counters(), (p0, o0)))
    if n == 1:  # a lone request runs as itself
        assert (programs, ops) == (0, 0)
    else:
        # BATCH_MAX requests a program at most; a further page's writes
        # and each read chunk past the first take a program each
        cls = st._classes[1024]
        pages = {(st._d[k] >> cache_store._LEN_BITS) // cls.rows_per_page
                 for k, _ in sets}
        chunks = lambda m: -(-m // cache_store.BATCH_MAX)  # noqa: E731
        assert ops == n
        assert chunks(max(len(sets), len(gets))) <= programs
        assert programs <= chunks(len(sets)) + len(pages) + chunks(len(gets))
    st.flush()


def test_a_row_freed_and_taken_again_in_one_batch():
    """``a`` moves to another size class, freeing its row; ``b``, new,
    takes that row; the GET of ``a`` in the same batch reads its new
    value, not ``b``'s."""
    st, ref = _loaded_store(n=128)  # two full pages: no fresh rows
    sets = [(b"k1", b"a" * 100), (b"new", b"b" * 1000), (b"k2", b"c" * 1000),
            (b"k2", b"d" * 1000)]  # and two SETs of one key: the last wins
    gets = [b"k1", b"new", b"k2", b"k3"]
    row_of = lambda k: st._d[k] >> cache_store._LEN_BITS  # noqa: E731
    freed = row_of(b"k1")
    _check_batch(st, ref, sets, gets)
    assert row_of(b"new") == freed
    st.flush()


@pytest.mark.parametrize("seed", [0, 13, 18, 21, 24])
def test_batches_that_evict_keep_every_live_value(seed):
    """A 16 KiB budget (pages of 1 KiB): a batch's SETs evict keys, its
    own among them, and give pages back (a page's pending writes and a
    class's whole share of the batch with it).  Each GET reads the last
    value SET or nothing if its key is gone, every key left reads its
    last value, and the HBM held stays within the budget."""
    st = HBMCacheStore(hbm_budget_bytes=16 << 10)
    rng = np.random.default_rng(seed)
    keys = [b"e%d" % i for i in range(30)]
    ref = {}
    for _ in range(60):
        sets, gets = _random_batch(rng, keys, int(rng.integers(2, 12)),
                                   lengths=(50, 300, 1000))
        stored, values = st.apply_batch(sets, gets)
        assert stored == [True] * len(sets)
        for k, v in sets:
            ref[k] = v
        live = set(st.keys())
        ref = {k: v for k, v in ref.items() if k in live}
        assert [_bytes(v) for v in values] == [ref.get(k) for k in gets]
        assert st.hbm_held <= st.budget
    # (read last: a GET touches recency, and so the evictions)
    assert {k: _bytes(st.get(k)) for k in st.keys()} == ref
    st.flush()


def test_batch_program_traces_are_bounded_by_the_buckets():
    """After the first batch of a (width, length), batches of every size
    1-64 trace nothing more: at most len(BATCH_BUCKETS) <= 7 variants."""
    st, ref = _loaded_store(length=777)
    prog = cache_store._slab_programs(jax.devices()[0].platform)["batch"]
    before = prog._cache_size()
    rng = np.random.default_rng(5)
    _check_batch(st, ref, *_random_batch(rng, list(ref), 2))
    warmed = prog._cache_size()
    assert warmed - before <= len(cache_store.BATCH_BUCKETS) <= 7
    for n in range(1, 65):
        _check_batch(st, ref, *_random_batch(rng, list(ref), n))
    assert prog._cache_size() == warmed
    st.flush()


def test_the_batch_programs_module_name_is_a_slab_program():
    """benchmark/slab_kernels.py finds the slab programs in a trace by
    their XLA module names; the batch program must be one of them, or
    ``slab_roofline_pct`` reads nothing."""
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(os.path.dirname(here), "benchmark")
    sys.path.insert(0, bench)  # slab_kernels imports tracereduce
    try:
        spec = importlib.util.spec_from_file_location(
            "_slab_kernels", os.path.join(bench, "slab_kernels.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    prog = cache_store._slab_programs(jax.devices()[0].platform)["batch"]
    page = jax.ShapeDtypeStruct((64, 1024), np.uint8)
    packed, _ = cache_store._batch_buffer(2, 1024, 64)
    text = prog.lower(page, (page, page), packed, length=1000).compile().as_text()
    name = re.search(r"HloModule (\S+?)[,\s]", text).group(1)
    assert name.startswith("jit_cache_slab_scatter_gather")
    assert re.search(mod.SLAB_PROGRAMS, name)


# ---- over the redis front ---------------------------------------------------


@pytest.fixture
def ici_cache():
    svc = HBMCacheService(hbm_budget_bytes=BUDGET)
    srv = Server(ServerOptions(redis_service=svc))
    s = _slice()
    assert srv.start_ici(s, 1) == 0
    chans = []

    def channel():
        ch = Channel(ChannelOptions(protocol="redis", timeout_ms=30000))
        assert ch.init(f"ici://slice{s}/chip1") == 0
        chans.append(ch)
        return ch

    yield svc, channel
    for ch in chans:
        ch.close()
    srv.stop()
    svc.store.flush()


def _pipeline(ch, *cmds):
    """One frame of commands; their replies in order."""
    req = R.RedisRequest()
    for cmd in cmds:
        req.add_command(*cmd)
    resp = R.RedisResponse()
    ctrl = Controller()
    ch.call_method(R.redis_method_spec(), ctrl, req, resp)
    assert not ctrl.failed(), ctrl.error_text()
    return [resp.reply(i) for i in range(len(cmds))]


def _value(r):
    if r.is_nil():
        return None
    if r.is_error():
        return ("error", r.value)
    arr = r.device_array()
    return _bytes(arr) if arr is not None else r.bytes_value()


def _expect(ref, cmds):
    """Each command in order, as the dict sees it."""
    out = []
    for cmd in cmds:
        if cmd[0] == "SET":
            ref[cmd[1]] = cmd[2]
            out.append("OK")
        else:
            out.append(ref.get(cmd[1]))
    return out


def _got(replies):
    return ["OK" if r.value == "OK" else _value(r) for r in replies]


def test_a_set_and_a_get_of_one_key_in_one_frame(ici_cache):
    svc, channel = ici_cache
    ch = channel()
    p0, o0 = _batch_counters()
    cmds = [("SET", b"a", b"1" * 1000), ("GET", b"a"), ("SET", b"a", b"2" * 1000),
            ("SET", b"a", b"3" * 1000), ("GET", b"a"), ("GET", b"zz")]
    ref = {}
    assert _got(_pipeline(ch, *cmds)) == _expect(ref, cmds)
    # a GET then a SET of its key: the GET reads the value before it
    cmds = [("GET", b"a"), ("SET", b"a", b"4" * 1000), ("GET", b"a")]
    assert _got(_pipeline(ch, *cmds)) == _expect(ref, cmds)
    # batches: [SET, GET], [SET, SET, GET, GET], [GET] (alone: no
    # program of its own), [SET, GET]
    programs, ops = (a - b for a, b in zip(_batch_counters(), (p0, o0)))
    assert (programs, ops) == (3, 8)


def test_pipelined_batches_match_the_reference(ici_cache):
    svc, channel = ici_cache
    ch = channel()
    rng = np.random.default_rng(11)
    ref = {}
    keys = [b"p%d" % i for i in range(40)]
    for _ in range(30):
        cmds = []
        for _ in range(int(rng.integers(1, 20))):
            k = keys[int(rng.integers(len(keys)))]
            if rng.random() < 0.5:
                cmds.append(("SET", k, rng.bytes(int(rng.choice([50, 1000])))))
            else:
                cmds.append(("GET", k))
        assert _got(_pipeline(ch, *cmds)) == _expect(ref, cmds)


def test_other_commands_flush_the_batch_first(ici_cache):
    """DEL, DMSET, MGET and EXISTS mid-frame see every deferred command
    before them, and the frame's replies keep its order."""
    svc, channel = ici_cache
    ch = channel()
    v = [bytes([i]) * 1000 for i in range(5)]
    replies = _pipeline(
        ch, ("SET", b"a", v[0]), ("GET", b"a"), ("DEL", b"a"), ("GET", b"a"),
        ("SET", b"a", v[1]), ("MGET", b"a", b"b"), ("SET", b"b", v[2]),
        ("DMSET", b"a", v[3], b"c", v[4]), ("GET", b"a"), ("EXISTS", b"b"),
        ("GET", b"c"))
    assert replies[0].value == "OK"
    assert _value(replies[1]) == v[0]
    assert replies[2].value == 1
    assert replies[3].is_nil()
    assert replies[4].value == "OK"
    assert [_value(x) for x in replies[5].value] == [v[1], None]
    assert replies[6].value == "OK"
    assert replies[7].value == 2
    assert _value(replies[8]) == v[3]
    assert replies[9].value == 1
    assert _value(replies[10]) == v[4]


def test_a_raising_program_answers_every_member_with_an_error(ici_cache,
                                                             monkeypatch):
    svc, channel = ici_cache
    ch = channel()
    assert _got(_pipeline(ch, ("SET", b"a", b"x" * 1000))) == ["OK"]

    def broken(*_a, **_k):
        raise RuntimeError("program refused")

    monkeypatch.setattr(HBMCacheStore, "_batch_program", broken)
    replies = _pipeline(ch, ("SET", b"b", b"y" * 1000), ("GET", b"a"),
                        ("GET", b"a"))
    assert all(r.is_error() and "program refused" in r.value for r in replies)
    monkeypatch.undo()
    # the server answers on: a lone GET, then a batch
    assert _value(_pipeline(ch, ("GET", b"a"))[0]) == b"x" * 1000
    assert _got(_pipeline(ch, ("SET", b"c", b"z" * 1000), ("GET", b"c"))) == [
        "OK", b"z" * 1000]


def test_a_tcp_clients_get_spills_at_once():
    svc = HBMCacheService(hbm_budget_bytes=BUDGET)
    srv = Server(ServerOptions(redis_service=svc))
    assert srv.start(0) == 0
    ch = Channel(ChannelOptions(protocol="redis", timeout_ms=5000))
    assert ch.init(f"127.0.0.1:{srv.port}") == 0
    try:
        p0 = _batch_counters()
        replies = _pipeline(ch, ("SET", b"k", b"t" * 1000), ("GET", b"k"),
                            ("GET", b"k"))
        assert replies[0].value == "OK"
        for r in replies[1:]:
            assert r.device_array() is None  # host clients get exact bytes
            assert r.bytes_value() == b"t" * 1000
        assert _batch_counters() == p0  # nothing deferred
    finally:
        ch.close()
        srv.stop()
        svc.store.flush()


def test_eight_concurrent_callers_share_batch_programs(ici_cache):
    """Each caller owns its keys, so a dict per caller is its exact
    reference whatever the other callers' commands in the same drain
    batch; the shared keys are read only.  Under this concurrency a
    batch program serves more than one request on average."""
    svc, channel = ici_cache
    shared = {b"s%d" % i: bytes([i]) * 1000 for i in range(20)}
    for k, v in shared.items():
        assert svc.store.set(k, v)
    chans = [channel() for _ in range(8)]
    p0, o0 = _batch_counters()
    errs = []

    def work(i):
        rng = np.random.default_rng(100 + i)
        ref = {}
        mine = [b"c%d-%d" % (i, j) for j in range(10)]
        try:
            for _ in range(60):
                if rng.random() < 0.3:
                    k = list(shared)[int(rng.integers(len(shared)))]
                    got = _got(_pipeline(chans[i], ("GET", k)))
                    assert got == [shared[k]]
                    continue
                k = mine[int(rng.integers(len(mine)))]
                cmd = (("SET", k, rng.bytes(1000)) if rng.random() < 0.5
                       else ("GET", k))
                assert _got(_pipeline(chans[i], cmd)) == _expect(ref, [cmd])
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)

    ths = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert errs == []
    programs, ops = (a - b for a, b in zip(_batch_counters(), (p0, o0)))
    assert programs > 0 and ops / programs > 1


def test_drain_scopes_nest_and_flush_in_arrival_order():
    from incubator_brpc_tpu.runtime import drain

    seen = []
    assert drain.current_drain() is None
    outer_prev = drain.open_drain()
    outer = drain.current_drain()
    outer.defer(seen.append, "a")
    outer.defer(seen.append, "b")
    inner_prev = drain.open_drain()  # a drain served inline inside
    assert inner_prev is outer
    drain.current_drain().defer(seen.append, "inner")
    drain.close_drain(inner_prev)
    assert seen == [["inner"]] and drain.current_drain() is outer
    drain.close_drain(outer_prev)
    assert seen == [["inner"], ["a", "b"]]
    assert drain.current_drain() is None
