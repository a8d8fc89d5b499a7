"""Kernels: device time of the flash-attention kernels (by their Pallas
``name=``) over the device's busy time."""

KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")


def read(trace, spans, counters, cell):
    if not trace:
        return None
    t = trace.seconds_of_kernels(KERNELS)
    if t <= 0:
        return None
    return 100.0 * t / trace.busy_s
