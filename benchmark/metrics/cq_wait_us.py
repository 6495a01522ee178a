"""cq_wait_us: completion-queue wait per request, µs: the request
frame's and the reply frame's time from ``received_us`` (the fabric
accepted it) to ``dequeued_us`` (the drain picked it up).  The mean over the complete
requests of the program's rpcz capture of the traced stretch
(``rpcz_capture.py``); None where it holds none.  Read for every
``cq_wait_us.<group>``."""

import rpcz_capture


def read(run):
    return rpcz_capture.run_mean(run, rpcz_capture.cq_wait_us)
