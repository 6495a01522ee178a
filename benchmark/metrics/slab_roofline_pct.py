"""slab_roofline_pct: the least time of the cache slab programs over
their device time.

Least time: programs × the bytes a program must move
(``slab_kernels.slab_row_bytes`` of the record: one read and one write
of the row; in a YCSB request each program moves one row) over the
chip's HBM peak.  Device time: the summed durations of the programs
(``slab_kernels.SLAB_PROGRAMS``, as the trace's "XLA Modules" line
names them) that ran inside the requests completed in the traced
stretch.  None where none ran (a program without the slab)."""

from slab_kernels import programs_in_requests, slab_row_bytes


def read(run):
    if run.trace is None:
        return None
    n, kernel_s = programs_in_requests(run.trace, run.chips)
    if n == 0 or kernel_s <= 0:
        return None
    least_s = n * slab_row_bytes(run.bench.frame_bytes) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
