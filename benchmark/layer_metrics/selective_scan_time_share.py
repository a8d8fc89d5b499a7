"""Kernels: device time in the selective scan's two kernels
(``selective_scan_fwd``, under remat twice a layer and step, and
``selective_scan_bwd``), by name, over the device's busy time.  A program
without the kernels reads nothing."""

from .mamba_time_share import BACKWARD, FORWARD, kernel_seconds


def read(trace, spans, counters, cell):
    if not trace:
        return None
    took = kernel_seconds(trace)
    if took <= 0:
        return None
    cell["say"]("selective_scan_time_share: %s %.6f s in %g calls, %s %.6f s "
                "in %g calls"
                % (FORWARD, trace.seconds_of_kernels((FORWARD,)),
                   trace.count_of_kernels((FORWARD,)), BACKWARD,
                   trace.seconds_of_kernels((BACKWARD,)),
                   trace.count_of_kernels((BACKWARD,))))
    return 100.0 * took / trace.busy_s
