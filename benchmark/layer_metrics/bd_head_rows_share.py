"""Model code: the rows the head computes over the clean tokens S, per cent:
whole blocks of the masked rows (``monitor.train.lm_head_rows_share``, which
the trainer writes under a monitor session by the function the device code
takes its trip count from; the cell's driver opens one around its witness
and hands the registry on as ``counters``).  12 blocks of 512 of 8,192 read
75.  A run without the counter (a driver that opens no session) reads
nothing."""

NAME = "monitor.train.lm_head_rows_share"


def read(trace, spans, counters, cell):
    share = counters.get(NAME)
    if share is None:
        return None
    cell["say"]("bd_head_rows_share: %.3f %% of the rows (masked: %s)"
                % (100.0 * share,
                   counters.get("monitor.train.bd_masked_share")))
    return 100.0 * share
