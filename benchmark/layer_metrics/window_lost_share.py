"""Train driver: the share of the measured window's work that
``train_throughput`` (the median over slices) leaves out -- 1 minus units
over the whole window / the sustained rate.  The start on an idle device
and an empty pipe is in it in every run (0.02 to 0.5 % on one chip, 2.5 % on
four; my chip runs, PR 22); a stall of the host or of the feed path comes on top, and is what to
look for when this moves and ``train_throughput`` does not."""


def read(trace, spans, counters, cell):
    if not cell.get("throughput") or not cell.get("window_rate"):
        return None
    return 100.0 * (1.0 - cell["window_rate"] / cell["throughput"])
