"""Padded-stack device fusion for batch handlers.

``fused_stack_rows`` turns N same-shape device rows into ONE stacked
device execution: stack along a new leading axis, pad the batch dim up
to the policy bucket, run a single jitted kernel over the stack, hand
each row its slice back.  Device payloads never detour through host
bytes — the inputs are the jax.Arrays the IOBuf ``DeviceRef`` segments
already hold, and the outputs go back out as DeviceRefs.

Padding rows are DONATED from the caller's freelist (the Batcher's
per-method ``StagingRing``, below): steady state pads with recycled
buffers instead of allocating, and every pad returns to the ring right
after the stack copies it.  Pad VALUES are never read
(their output rows are discarded), so recycled contents are fine.

Because jit specializes on the leading dim, padding to buckets bounds
the trace cache at the bucket count; ``trace_count()`` exposes the
running total so tests can assert the bound.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from incubator_brpc_tpu.observability.profiling import (
    hbm_account,
    kernel_section,
)

# HBM heap profiler tag (observability/profiling.py): ring-resident
# freelist slots
_FREELIST_ACCT = hbm_account("batching.pad_freelist")

_trace_count = [0]
_jit_stack = None
# guards lazy jit construction (module stack kernel + every FusedKernel):
# racing first calls would each build a wrapper, double-tracing one shape
# and breaking the retraces <= buckets bound trace_count() exists to assert
_init_lock = threading.Lock()


class StagingRing:
    """Ring of persistent device buffers keyed by (shape, dtype) — the
    RDMA block_pool analog (rdma_endpoint.h:63-227 pre-registered
    memory), here the Batcher's pad freelist.

    The ring holds at most ``depth`` slots per key and at most
    ``max_keys`` shapes (LRU-evicted — a caller cycling many shapes
    degrades to plain allocation, never to unbounded HBM)."""

    def __init__(self, depth: int = 4, max_keys: int = 8):
        self.depth = depth
        self.max_keys = max_keys
        self._slots: Dict[Tuple, deque] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def acquire(self, shape, dtype):
        """A reusable buffer of (shape, dtype), or None (caller
        allocates; release() later seeds the ring).  An acquired slot
        leaves the ``batching.pad_freelist`` HBM ledger — it is the
        caller's memory until released back."""
        key = (tuple(shape), str(dtype))
        with self._lock:
            q = self._slots.get(key)
            if q:
                # LRU touch: move key to the back of the eviction order
                self._slots[key] = self._slots.pop(key)
                self.hits += 1
                arr, charge = q.popleft()
                _FREELIST_ACCT.release(charge)
                return arr
            self.misses += 1
            return None

    def release(self, arr) -> None:
        """Return a buffer to the ring.  Only call for buffers nothing
        downstream holds."""
        key = (tuple(arr.shape), str(arr.dtype))
        with self._lock:
            q = self._slots.get(key)
            if q is None:
                while len(self._slots) >= self.max_keys:
                    # LRU eviction: dict preserves insertion order and
                    # acquire() re-inserts on hit, so the first key is
                    # the least recently used
                    evq = self._slots.pop(next(iter(self._slots)))
                    for _, charge in evq:
                        _FREELIST_ACCT.release(charge)
                q = self._slots[key] = deque()
            if len(q) < self.depth:
                q.append((arr, _FREELIST_ACCT.adopt(arr)))

    def clear(self) -> None:
        with self._lock:
            for q in self._slots.values():
                for _, charge in q:
                    _FREELIST_ACCT.release(charge)
            self._slots.clear()


def trace_count() -> int:
    """Total traces of fused kernels so far (monotonic; tests diff it
    around a workload to assert padding bounds retraces).  Shared by the
    stack kernel below and every ``FusedKernel``."""
    return _trace_count[0]


class FusedKernel:
    """A user batch kernel jitted with the module's shared trace
    counter, so padding-bucket retrace bounds are assertable for custom
    fused ops exactly like for the built-in stack kernel.

        _FWD = FusedKernel(lambda w, x: x @ w)
        y = _FWD(W, X_padded)   # ONE device execution per call;
                                # retraces only per new padded shape

    ``label``/``batch_buckets`` opt the kernel into the retrace witness
    (analysis/device_witness.py): each retrace is attributed to a shape
    *family* — argument shapes/dtypes with the batch arg's (last
    positional, by fused convention) leading dim wildcarded — and a
    family retracing more than ``len(batch_buckets)`` times contradicts
    the padding bound and fails the witness lane.
    """

    __slots__ = ("_fn", "_jit", "label", "batch_buckets", "_traces",
                 "_families", "_section")

    def __init__(self, fn: Callable, label: Optional[str] = None,
                 batch_buckets=None):
        self._fn = fn
        self._jit = None
        self.label = label or getattr(fn, "__name__", "fused")
        self.batch_buckets = (
            tuple(batch_buckets) if batch_buckets is not None else None
        )
        self._traces = [0]
        self._families = {}
        # device-time attribution family (observability/profiling.py):
        # precomputed so the hot path never formats a string
        self._section = f"fused.{self.label}"

    def trace_count(self) -> int:
        """Traces of THIS kernel so far (the module-level
        ``trace_count()`` stays the shared total)."""
        return self._traces[0]

    def __call__(self, *args):
        if self._jit is None:
            with _init_lock:
                if self._jit is None:
                    import jax

                    fn = self._fn
                    mine = self._traces

                    def _traced(*a):
                        # runs at TRACE time only: one increment per
                        # distinct input-shape specialization
                        _trace_count[0] += 1
                        mine[0] += 1
                        return fn(*a)

                    self._jit = jax.jit(_traced)
        # the section times the DISPATCH window (async dispatch returns
        # immediately; paths with a manifested pull add their own wider
        # family, e.g. ps.forward) — it never syncs the device
        if self.batch_buckets is None:
            with kernel_section(self._section):
                return self._jit(*args)
        before = self._traces[0]
        with kernel_section(self._section):
            out = self._jit(*args)
        if self._traces[0] != before:
            self._note_retrace(args)
        return out

    def _note_retrace(self, args) -> None:
        fam = []
        for i, a in enumerate(args):
            shape = tuple(getattr(a, "shape", ()) or ())
            if i == len(args) - 1 and shape:
                shape = ("*",) + shape[1:]
            fam.append((shape, str(getattr(a, "dtype", ""))))
        fam = tuple(fam)
        with _init_lock:
            n = self._families.get(fam, 0) + 1
            self._families[fam] = n
        from incubator_brpc_tpu.analysis import device_witness

        device_witness.note_trace(
            self.label, fam, n, len(self.batch_buckets)
        )


def _get_jit():
    global _jit_stack
    if _jit_stack is None:
        with _init_lock:
            if _jit_stack is None:
                import jax
                import jax.numpy as jnp

                def _fused(xs):
                    # stack + copy fuse into ONE compiled kernel: a
                    # single device dispatch per batch instead of one
                    # eager stack plus one jitted pass (the eager stack
                    # alone costs more than the whole unbatched op at
                    # small shapes)
                    _trace_count[0] += 1
                    return jnp.stack(xs) + 0

                _jit_stack = jax.jit(_fused)
    return _jit_stack


def fused_stack_rows(arrays: List, pad_to: int, freelist=None) -> List:
    """One fused device execution over ``arrays`` (same shape/dtype),
    padded to ``pad_to`` rows.  Returns len(arrays) per-row outputs.

    ``freelist`` is a StagingRing-shaped pool (acquire(shape, dtype) /
    release(arr)); None pads with fresh zeros."""
    import jax.numpy as jnp

    n = len(arrays)
    if n == 0:
        return []
    proto = arrays[0]
    pad_to = max(pad_to, n)
    pads = []
    for _ in range(pad_to - n):
        slot = freelist.acquire(proto.shape, proto.dtype) if freelist is not None else None
        if slot is None:
            slot = jnp.zeros(proto.shape, proto.dtype)
        pads.append(slot)
    # jit specializes on the tuple length (= the padding bucket) and row
    # shape, so the trace cache stays bounded by the policy's buckets
    with kernel_section("fused.stack"):
        out = _get_jit()(tuple(arrays) + tuple(pads))
    # the stack copied every pad into the batch buffer (jax arrays are
    # immutable, so recycling the slot refs immediately is safe even
    # while the async dispatch still reads them)
    if freelist is not None:
        for s in pads:
            freelist.release(s)
    return [out[i] for i in range(n)]
