"""The drain scope: work deferred to the close of one completion-queue
drain batch.

A server port's drain (``parallel.ici.IciPort._drain_completions``)
runs every frame of a batch in turn on one thread.  While it does, a
``DrainScope`` is open on that thread (``current_drain``), and a
service may defer a command to the batch's close instead of running it
at once: the HBM cache defers its GETs and SETs of slab rows, so that
the batch's device work is one program (``cache/service.py``).  The
scope follows the fabric's ``delivery_burst`` thread-local pattern;
nested drains (a handler whose call is served inline on the same
thread) open scopes of their own, so no deferred reply waits on an
outer batch.  A scope's ``owner`` names the port whose batch of two or
more frames it spans: the fabric defers that port's small same-chip
reply transmits to the close, so the batch's copies are one program
(``parallel.ici.IciFabric.send``).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

_TLS = threading.local()


class DrainScope:
    """``defer(flush, item)`` collects ``item`` under ``flush``;
    ``flush()`` calls each ``flush(items)`` once, its items in arrival
    order.  Anything that must not overtake deferred work (a command
    that does not defer, a reply written at once) flushes first."""

    __slots__ = ("pending", "owner")

    def __init__(self, owner=None):
        self.pending: Dict[Callable[[List], None], List] = {}
        self.owner = owner

    def defer(self, flush: Callable[[List], None], item) -> None:
        self.pending.setdefault(flush, []).append(item)

    def flush(self) -> None:
        while self.pending:
            pending, self.pending = self.pending, {}
            for fn, items in pending.items():
                fn(items)

    def flush_one(self, flush: Callable[[List], None]) -> None:
        """Run ``flush``'s items alone, now."""
        items = self.pending.pop(flush, None)
        if items:
            flush(items)


def current_drain() -> Optional[DrainScope]:
    """The scope of the drain batch this thread is running, or None."""
    return getattr(_TLS, "scope", None)


def open_drain(owner=None) -> Optional[DrainScope]:
    """Open a scope on this thread; returns the one it replaces, for
    ``close_drain``."""
    prev = getattr(_TLS, "scope", None)
    _TLS.scope = DrainScope(owner)
    return prev


def close_drain(prev: Optional[DrainScope]) -> None:
    """Close this thread's scope and run what it deferred, with ``prev``
    (the scope ``open_drain`` replaced) back in place first: work the
    flush sets off is never deferred into the scope being flushed."""
    scope = _TLS.scope
    _TLS.scope = prev
    scope.flush()
