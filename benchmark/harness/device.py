"""No chip, no number: the device check and the device facts of the line."""

from .peaks import peaks_for


class NoChip(RuntimeError):
    pass


def check_devices(devices, chips):
    """The cell's devices and their peaks row, or ``NoChip`` /
    ``UnlistedDevice``: a run off a listed TPU prints no result."""
    if not devices or devices[0].platform != "tpu":
        raise NoChip("JAX found no TPU (platform %r): the benchmark measures "
                     "on the chip only" % (devices[0].platform if devices
                                           else None,))
    if len(devices) < chips:
        raise NoChip("the cell needs %d chips, JAX reports %d"
                     % (chips, len(devices)))
    return list(devices[:chips]), peaks_for(devices[0].device_kind)


def peak_bytes(stats):
    """The peak of one chip.  The v5e runtime keeps two disjoint books
    (seen on the chip, PR 22): ``peak_bytes_in_use`` counts buffers
    (weights, optimizer state, batches, outputs) and ``peak_bytes_reserved``
    the region it reserves for the loaded programs' temporaries, which for
    a training step is most of the memory (BERT-base B=64: 1.34 GB of
    buffers, 13.44 GB reserved; ``memory_analysis()`` of the same step says
    12.99 GB of temporaries).  The chip holds both at once."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def device_facts(all_devices, used):
    """``device`` of the last line: what JAX reports, and the peak on the
    fullest chip the cell used."""
    peak = max(peak_bytes(d.memory_stats() or {}) for d in used)
    return {"platform": all_devices[0].platform,
            "kind": all_devices[0].device_kind,
            "count": len(all_devices),
            "memory_peak_bytes": peak}
