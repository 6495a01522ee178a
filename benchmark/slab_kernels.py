"""The cache slab's programs in a trace: their names, the bytes each must
move, and their count and device time inside completed requests (the
yardstick's own, not the program's)."""

from __future__ import annotations

import bisect
import re
from typing import Sequence, Tuple

import tracereduce as tracing

# the XLA module names of cache/store.py's named jits
SLAB_PROGRAMS = r"cache_slab_(read|write|scatter|gather)"


def slab_row_bytes(row_bytes: int) -> int:
    """One row through a slab program: read once from HBM and written
    once (a GET's slice into its new buffer, an update into its page)."""
    return 2 * int(row_bytes)


def programs_in_requests(tr, chips: Sequence[int],
                         pattern: str = SLAB_PROGRAMS) -> Tuple[int, float]:
    """(count, summed device seconds) of the programs whose module name
    matches ``pattern`` and that lie wholly inside a request completed in
    the window."""
    rx = re.compile(pattern)
    reqs = tracing.union(tracing.completed_requests(tr))
    starts = [s for s, _ in reqs]
    n, total = 0, 0.0
    for c in chips:
        for e in tr.modules.get(c, []):
            if not rx.search(e.name):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= reqs[i][1]:
                n += 1
                total += e.end - e.start
    return n, total / 1e9
