"""client_us: the client's own work per request, µs: before its request
enters the fabric (client span start to request write) and after its
reply frame is picked up (dequeued to end).  The mean over the complete
requests of the program's rpcz capture of the traced stretch
(``rpcz_capture.py``); None where it holds none.  Read for every
``client_us.<group>``."""

import rpcz_capture


def read(run):
    return rpcz_capture.run_mean(run, rpcz_capture.client_us)
