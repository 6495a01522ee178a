"""transmit_roofline_pct: the least time of the same-chip transmits
over their device time.

Least time: hops × the bytes a hop must move (``kernels.transmit_bytes``:
one read and one write of the frame) over the chip's HBM peak.  Device
time: the summed durations of the transmit programs (named below, as
they appear on the trace's "XLA Modules" line) that ran inside the
requests completed in the traced stretch.  None when no transmit
program ran there (another lane took the frames)."""

import tracereduce as tracing

from kernels import transmit_bytes

TRANSMIT_PROGRAMS = r"copy_with_checksum|_chunked_copy_csum|_transmit_reshaped"


def read(run):
    if run.trace is None or not run.bench.hops_per_request:
        return None
    kernel_s = tracing.program_time_in_requests(
        run.trace, run.chips, TRANSMIT_PROGRAMS)
    if kernel_s <= 0:
        return None
    hops = len(tracing.completed_requests(run.trace)) * run.bench.hops_per_request
    least_s = hops * transmit_bytes(run.bench.frame_bytes) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
