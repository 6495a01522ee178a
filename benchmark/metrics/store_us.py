"""store_us: the store's part of a cache command per request, µs: the
server span's ``store_done_us`` − ``store_start_us`` (stamped by
``HBMCacheService`` around its store call).  The mean over the
complete requests of the program's rpcz capture of the traced stretch
(``rpcz_capture.py``) that carry both stamps; None where none does (a
program without the stamps).  Read for every ``store_us.<group>``."""

import rpcz_capture


def store_us(r):
    s = r.server
    start = getattr(s, "store_start_us", 0)
    done = getattr(s, "store_done_us", 0)
    return float(done - start) if start and done else None


def read(run):
    if getattr(run, "trace", None) is None:
        return None
    parts = [p for p in map(store_us, rpcz_capture.requests(
        rpcz_capture.last_capture())) if p is not None]
    return sum(parts) / len(parts) if parts else None
