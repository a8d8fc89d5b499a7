"""Kernels: device time of the flash-attention kernels of a stack that mixes
full and windowed layers (by their Pallas ``name=``: a windowed call's name
starts ``flash_swa_``) over the device's busy time.  A program without the
windowed kernels reads what its full ones took, and one without flash
kernels nothing."""

FULL = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")
WINDOWED = ("flash_swa_fwd", "flash_swa_bwd_fused", "flash_swa_bwd_dq",
            "flash_swa_bwd_dkv")


def read(trace, spans, counters, cell):
    if not trace:
        return None
    t = trace.seconds_of_kernels(FULL + WINDOWED)
    if t <= 0:
        return None
    return 100.0 * t / trace.busy_s
