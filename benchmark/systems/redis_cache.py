"""YCSB core workloads on the HBM cache behind the redis front, over ICI.

The configuration names the deployment (record count and layout, the
chip, the store's HBM budget, replicas) and the traffic names the mix
(read and update shares, the key distribution, callers).  Records are
YCSB's ``fieldcount`` × ``fieldlength`` bytes stored as one value; an
update rewrites the whole record.  The load phase SETs every record
through the same front before the window.

Guarantee checked (``check``): one replica, no eviction, so every
sampled GET returns a value that a linearizable register could return:
the record's load or a SET issued before the GET completed and not
overwritten by a SET that completed before the GET was issued.  The
plain reference is this rule over the log of SETs, with each value's
bytes made again from the seed.
"""

from __future__ import annotations

import bisect
import struct
import threading
import time

import numpy as np

from generator import Reservoir
from seeds import rng

HEADER = struct.Struct("<QQ")  # key index, version
CHUNK = 1 << 16
LOAD_BATCH = 100  # records per DMSET in the load phase


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    c = np.cumsum(w)
    return c / c[-1]


class Values:
    """Record bytes from the seed: a header (key, version) and a slice
    of a seeded pool, so every version of every record differs."""

    def __init__(self, seed: int, size: int):
        self.size = size
        self.pool = rng(seed, 4).bytes(1 << 20)
        self.span = len(self.pool) - size

    def make(self, key: int, version: int) -> bytes:
        off = (key * 7919 + version * 104729) % self.span
        return HEADER.pack(key, version) + self.pool[off:off + self.size - HEADER.size]


class Mix:
    """Each caller's stream of (is_update, key), drawn in chunks."""

    def __init__(self, seed: int, caller: int, tr: dict, perm, cdf):
        self.rng = rng(seed, 3, caller)
        self.upd = float(tr["updateproportion"])
        self.perm, self.cdf = perm, cdf
        self.ops = np.zeros(0, bool)
        self.keys = np.zeros(0, np.int64)
        self.base = 0

    def op(self, k: int):
        j = k - self.base
        if not 0 <= j < len(self.ops):
            self.base, j = k, 0
            self.ops = self.rng.random(CHUNK) < self.upd
            ranks = np.searchsorted(self.cdf, self.rng.random(CHUNK))
            self.keys = self.perm[np.minimum(ranks, len(self.perm) - 1)]
        return bool(self.ops[j]), int(self.keys[j])


class _Caller:
    def __init__(self, bench, idx, channel):
        import incubator_brpc_tpu.protocols.redis as R
        from incubator_brpc_tpu.client.controller import Controller

        self.R, self.Controller = R, Controller
        self.spec = R.redis_method_spec()
        self.bench = bench
        self.idx = idx
        self.ch = channel
        self.mix = bench.mix(idx)
        self.version = 0
        self.sets = []  # (key, version, start, end, ok)
        self.sample = Reservoir(bench.per_caller_sample, rng(bench.seed, 2, idx))

    def command(self, *cmd):
        R = self.R
        req = R.RedisRequest()
        req.add_command(*cmd)
        resp = R.RedisResponse()
        c = self.Controller()
        self.ch.call_method(self.spec, c, req, resp)
        if c.failed():
            return None
        return resp.reply(0)

    def call(self, k: int) -> bool:
        is_update, key = self.mix.op(k)
        kb = b"user%d" % key
        if is_update:
            self.version += 1
            ver = ((self.idx + 1) << 40) | self.version
            s = time.perf_counter_ns()
            r = self.command("SET", kb, self.bench.values.make(key, ver))
            ok = r is not None and not r.is_error()
            self.sets.append((key, ver, s, time.perf_counter_ns(), ok))
            return ok
        s = time.perf_counter_ns()
        r = self.command("GET", kb)
        if r is None or r.is_error():
            return False
        arr = r.device_array()
        if arr is not None:
            arr.block_until_ready()
            value = arr
        else:
            value = r.bytes_value()
        e = time.perf_counter_ns()
        self.sample.offer(lambda: (key, s, e, value))
        return True


class _ControlCaller(_Caller):
    """The reference store put in the program's place, with the
    guarantee broken: a SET is acknowledged and not applied."""

    def __init__(self, bench, idx):
        self.bench, self.idx = bench, idx
        self.mix = bench.mix(idx)
        self.version = 0
        self.sets = []
        self.sample = Reservoir(bench.per_caller_sample, rng(bench.seed, 2, idx))

    def command(self, op, kb, value=None):
        if op == "SET":
            return _Ok()  # acknowledged, never stored
        return _Bulk(self.bench.control_store[kb])


class _Ok:
    def is_error(self):
        return False


class _Bulk(_Ok):
    def __init__(self, v):
        self.v = v

    def device_array(self):
        return None

    def bytes_value(self):
        return self.v


class CacheBench:
    def __init__(self, cell, devices, seed, control=False):
        from incubator_brpc_tpu.cache import HBMCacheService
        from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
        from incubator_brpc_tpu.server.server import Server, ServerOptions

        cfg, tr = cell.config, cell.traffic
        self.seed = seed
        self.n = int(cfg["recordcount"])
        self.size = int(cfg["fieldcount"]) * int(cfg["fieldlength"])
        self.values = Values(seed, self.size)
        self.tr = tr
        if tr["requestdistribution"] != "zipfian":
            raise ValueError(f"requestdistribution {tr['requestdistribution']!r}: "
                             "only zipfian is generated")
        if float(tr["readproportion"]) + float(tr["updateproportion"]) != 1.0:
            raise ValueError("only reads and whole-record updates are generated")
        self._perm = rng(seed, 5).permutation(self.n)
        self._cdf = zipf_cdf(self.n, float(tr["zipfian_constant"]))
        chip = cfg["chip"]
        self.dev = devices[chip]
        self.chips = [chip]
        self.bytes_per_request = None
        self.hops_per_request = None
        self.frame_bytes = self.size
        ncallers = int(tr["callers_per_server"])
        self.per_caller_sample = max(1, int(tr["sample"]) // ncallers)
        self.servers, self.channels, self.callers = [], [], []
        if control:
            self.control_store = {b"user%d" % k: self.values.make(k, 0)
                                  for k in range(self.n)}
            self.callers = [_ControlCaller(self, i) for i in range(ncallers)]
            return
        self.svc = HBMCacheService(device=self.dev, **cfg.get("store_options", {}))
        srv = Server(ServerOptions(redis_service=self.svc,
                                   **cfg.get("server_options", {})))
        if srv.start_ici(0, chip, device=self.dev) != 0:
            raise RuntimeError(f"start_ici on chip {chip} failed")
        self.servers.append(srv)
        opts = dict(cfg.get("channel_options", {}))
        for i in range(ncallers):
            ch = Channel(ChannelOptions(protocol="redis", ici_device=self.dev,
                                        **opts))
            if ch.init(f"ici://slice0/chip{chip}") != 0:
                raise RuntimeError("redis channel over ICI failed")
            self.channels.append(ch)
            self.callers.append(_Caller(self, i, ch))
        self._load()

    def mix(self, idx):
        return Mix(self.seed, idx, self.tr, self._perm, self._cdf)

    def _load(self):
        """YCSB's load phase: every record stored once, through the same
        front, as DMSETs of LOAD_BATCH records spread over the callers'
        channels (one SET per record would cost each run ~10 s of set-up
        that serves no request)."""
        errs = []
        batches = [range(k, min(k + LOAD_BATCH, self.n))
                   for k in range(0, self.n, LOAD_BATCH)]

        def load(i):
            c = self.callers[i]
            for b in batches[i::len(self.callers)]:
                kv = []
                for k in b:
                    kv += [b"user%d" % k, self.values.make(k, 0)]
                r = c.command("DMSET", *kv)
                if r is None or r.is_error() or r.value != len(b):
                    errs.append(b.start)

        ths = [threading.Thread(target=load, args=(i,))
               for i in range(len(self.callers))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        if errs:
            raise RuntimeError(f"{len(errs)} load batches failed")

    def counters(self) -> dict:
        from incubator_brpc_tpu.cache.store import (
            cache_evictions, cache_hbm_bytes, cache_hits, cache_misses)
        from incubator_brpc_tpu.parallel import ici

        return {
            "rpc_cache_hits": int(cache_hits.get_value()),
            "rpc_cache_misses": int(cache_misses.get_value()),
            "rpc_cache_evictions": int(cache_evictions.get_value()),
            "rpc_cache_hbm_bytes": int(cache_hbm_bytes.get_value()),
            "rpc_ici_unchecked_segments": int(ici.ici_unchecked_segments.get_value()),
        }

    def close(self):
        for ch in self.channels:
            ch.close()
        for srv in self.servers:
            srv.stop()
        self.channels, self.servers = [], []
        if hasattr(self, "svc"):
            self.svc.store.flush()

    def check(self, failed: int) -> dict:
        """``wrong_answers``: sampled GETs that no linearizable register
        could have returned, and requests that failed."""
        sets = {}
        for c in self.callers:
            for key, ver, s, e, ok in c.sets:
                sets.setdefault(key, []).append((ver, s, e, ok))
        index = {}
        for key, ws in sets.items():
            done = sorted((e, s) for _, s, e, ok in ws if ok)
            ends = [e for e, _ in done]
            pmax = list(np.maximum.accumulate([s for _, s in done])) if done else []
            index[key] = ({v: (s, e) for v, s, e, _ in ws}, ends, pmax)
        wrong = sampled = 0
        for c in self.callers:
            for key, s, e, value in c.sample.items:
                sampled += 1
                if not self._legal(key, s, e, value, index.get(key)):
                    wrong += 1
        return {
            "parts": {"not_linearizable": wrong, "failed": failed},
            "sampled_reads": {"value": sampled, "limit": 1, "at_least": True},
            "wrong_answers": {"value": wrong + failed, "limit": 0},
        }

    def _legal(self, key, s, e, value, idx) -> bool:
        if value is None:
            return False
        if not isinstance(value, (bytes, bytearray)):
            value = np.asarray(value).tobytes()
        if len(value) != self.size:
            return False
        k2, ver = HEADER.unpack_from(value)
        if k2 != key or value != self.values.make(key, ver):
            return False
        versions, ends, pmax = idx if idx else ({}, [], [])
        j = bisect.bisect_left(ends, s)  # SETs that completed before s
        latest_start = pmax[j - 1] if j else None
        if ver == 0:
            return latest_start is None
        if ver not in versions:
            return False
        ws, we = versions[ver]
        return ws < e and (latest_start is None or we >= latest_start)


def build(cell, devices, seed, control=False):
    return CacheBench(cell, devices, seed, control=control)
