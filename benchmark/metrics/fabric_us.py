"""fabric_us: placement and transmit dispatch per request, µs: the sum
over its ICI leg spans of start to ``placed_us`` (both hops).  The mean over the complete
requests of the program's rpcz capture of the traced stretch
(``rpcz_capture.py``); None where it holds none.  Read for every
``fabric_us.<group>``."""

import rpcz_capture


def read(run):
    return rpcz_capture.run_mean(run, rpcz_capture.fabric_us)
