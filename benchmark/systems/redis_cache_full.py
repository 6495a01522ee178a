"""YCSB on an HBM cache node that holds half of the chip.

The ``redis_cache`` adapter (loaded by path, below) with the same mix,
callers and check — the plain reference is its linearizable-register
rule over the log of SETs, each value's bytes made again from the
seed — and two things of its own:

- the load: every record stored once through the same front, as fused
  DMSETs of ``LOAD_BATCH`` records (``DMSET 1 <lengths> <stacked> <key
  lengths> <keys>``, encoded by the program's ``dmset_fused_command``),
  each batch's values one (B, L) device array made by ``BulkValues``,
  bit-equal to ``Values.make``;
- a probe before the load: one fused DMSET, which raises at once where
  the program has no such form (no encoder, or a server that refuses
  it), instead of loading millions of records one SET at a time.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import threading
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "_bench_system_redis_cache",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "redis_cache.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

LOAD_BATCH = 4096  # records per fused DMSET in the load phase
LOAD_CHANNELS = 2  # callers' channels the load spreads over


class NoFusedDmset(RuntimeError):
    pass


class BulkValues:
    """``Values.make`` for many keys at once, bit-equal to it (for
    versions under 2**40, where its offsets fit in 64 bits), made where
    the values go: the seeded pool put on the device once, and each
    batch's bodies sliced out of it there, so only the headers and the
    offsets cross from the host."""

    def __init__(self, values: "base.Values"):
        self.size = values.size
        self.span = values.span
        self.pool = np.frombuffer(values.pool, np.uint8)
        self._pools: dict = {}  # device -> the pool on it
        self._make = None

    def rows(self, keys, device, version=0):
        """(len(keys), size) uint8 on ``device``: row i is the value of
        keys[i]."""
        import jax

        if self._make is None:
            import jax.numpy as jnp
            from jax import lax

            body = self.size - base.HEADER.size

            def make(pool, head, off):
                bodies = jax.vmap(
                    lambda o: lax.dynamic_slice(pool, (o,), (body,)))(off)
                return jnp.concatenate([head, bodies], axis=1)

            self._make = jax.jit(make)
        pool = self._pools.get(device)
        if pool is None:
            pool = self._pools[device] = jax.device_put(self.pool, device)
        keys = np.asarray(keys, np.int64)
        ver = np.broadcast_to(np.asarray(version, np.int64), keys.shape)
        off = ((keys * 7919 + ver * 104729) % self.span).astype(np.int32)
        head = np.stack([keys, ver], axis=1).astype("<u8").view(np.uint8)
        return self._make(pool, jax.device_put(head, device),
                          jax.device_put(off, device))


# full collections of the Python heap and their pauses, for the run's
# counters: each walks the store's index, one entry a record
_gc_full = {"n": 0, "us": 0, "t0": 0}


def _on_gc(phase, info):
    if info.get("generation") != 2:
        return
    now = time.perf_counter_ns()
    if phase == "start":
        _gc_full["t0"] = now
    else:
        _gc_full["n"] += 1
        _gc_full["us"] += (now - _gc_full["t0"]) // 1000


class FullCacheBench(base.CacheBench):
    def _batch(self, bulk: BulkValues, lo: int, hi: int) -> tuple:
        """The fused DMSET that stores records lo..hi-1, each row whole."""
        keys = [b"user%d" % k for k in range(lo, hi)]
        rows = bulk.rows(np.arange(lo, hi), self.dev)
        return self._encode(keys, rows, [rows.shape[1]] * len(keys))

    def _load(self):
        """YCSB's load phase: a probe of one record, then the rest as
        fused DMSETs of LOAD_BATCH records spread over the callers'
        channels.  A failed load stops the server and channels: the
        caller never gets the bench to close."""
        try:
            self._load_fused()
        except BaseException:
            self.close()
            raise

    def _load_fused(self):
        try:
            from incubator_brpc_tpu.cache.channel import dmset_fused_command
        except ImportError as e:
            raise NoFusedDmset(f"the program has no fused DMSET ({e}); "
                               f"loading {self.n} records needs it") from e
        self._encode = dmset_fused_command
        bulk = BulkValues(self.values)
        r = self.callers[0].command(*self._batch(bulk, 0, 1))
        if r is None or r.is_error() or r.value != 1:
            raise NoFusedDmset(
                "the program stored no record through a fused DMSET "
                f"(reply {r!r}); loading {self.n} records needs it")
        batches = [(k, min(k + LOAD_BATCH, self.n))
                   for k in range(1, self.n, LOAD_BATCH)]
        errs = []

        def load(i):
            c = self.callers[i]
            for lo, hi in batches[i::min(LOAD_CHANNELS, len(self.callers))]:
                r = c.command(*self._batch(bulk, lo, hi))
                if r is None or r.is_error() or r.value != hi - lo:
                    errs.append(lo)

        ths = [threading.Thread(target=load, args=(i,))
               for i in range(min(LOAD_CHANNELS, len(self.callers)))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        if errs:
            raise RuntimeError(f"{len(errs)} load batches failed")

    def counters(self) -> dict:
        from incubator_brpc_tpu.cache import store

        out = super().counters()
        for name in ("slab_pages", "slab_rows", "slab_writes",
                     "slab_write_programs"):
            c = getattr(store, name, None)
            if c is not None:
                out["rpc_cache_" + name] = int(c.get_value())
        out["gc_full_collections"] = _gc_full["n"]
        out["gc_full_pause_us"] = _gc_full["us"]
        return out


def build(cell, devices, seed, control=False):
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return FullCacheBench(cell, devices, seed, control=control)
