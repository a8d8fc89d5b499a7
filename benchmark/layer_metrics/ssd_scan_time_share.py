"""Kernels: device time in the chunked scan's two kernels (``ssd_scan_fwd``,
under remat twice a layer and step, and ``ssd_scan_bwd``), by name, over the
device's busy time.  A program without the kernels reads nothing."""

from .mamba2_time_share import kernel_seconds, said_kernels


def read(trace, spans, counters, cell):
    if not trace:
        return None
    took = kernel_seconds(trace)
    if took <= 0:
        return None
    cell["say"]("ssd_scan_time_share: " + said_kernels(trace))
    return 100.0 * took / trace.busy_s
