"""Operations the job requires, and the roofline arithmetic.

The counting functions are files of their own, ``benchmark/flops/<name>.py``:
a configuration names the one that gives its required FLOPs per unit of work
(``"flops": "<name>"``, the file's ``per_unit(model, dims)``), and a
kernel's roofline reader imports the one that gives that kernel's FLOPs and
bytes.  A new model family or kernel brings a new file.
"""

from . import manifest as mf


def per_unit(config, dims):
    """Required FLOPs per unit of work of a configuration, or None where
    its file names no counting function."""
    if "flops" not in config:
        return None
    return mf.module("flops", config["flops"]).per_unit(config["model"], dims)


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which roof binds."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
