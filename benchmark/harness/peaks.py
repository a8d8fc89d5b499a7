"""Published per-chip peaks, keyed by the ``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip, 1,600
Gbit/s of chip-to-chip interconnect.  The kind string "TPU v5 lite" was read
off the chip (PERF.md, PR 21).  Copied from ``bench.PEAKS`` (the original is
listed for deletion under Open questions in PERF.md).

A device that is not listed has no roofline and no MFU: ``peaks_for``
raises, it never defaults.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnlistedDevice(RuntimeError):
    pass


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise UnlistedDevice(
            "no published peaks for device_kind %r: add its row to "
            "benchmark/harness/peaks.py with the source" % (device_kind,))
    return PEAKS[device_kind]
