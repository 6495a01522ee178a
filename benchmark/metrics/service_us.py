"""service_us: the handler per request, µs: the server span's
``callback_start_us`` to ``callback_done_us`` (``EchoService.Echo``;
``HBMCacheService`` with its store).  The mean over the complete
requests of the program's rpcz capture of the traced stretch
(``rpcz_capture.py``); None where it holds none.  Read for every
``service_us.<group>``."""

import rpcz_capture


def read(run):
    return rpcz_capture.run_mean(run, rpcz_capture.service_us)
