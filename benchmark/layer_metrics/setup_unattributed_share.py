"""Train driver / set-up: of ``bench.build`` + ``bench.warmup``, the share
inside no phase and no record of the program's compile ledger, less the
device time of the warm-up's own steps (at the window's median step): the
instrument's own coverage.  Over 20 %, do not believe ``setup_init_s``,
``setup_trace_lower_s`` and ``setup_compile_s`` before the log's "in no
record" lines have said where the seconds are."""

from ..harness import setup_time

GAPS = 4      # the longest stretches in no record, named in the log


def read(trace, spans, counters, cell):
    got = setup_time.split(spans, cell)
    if got is None or not got["base_s"]:
        return None
    parts = got["init_s"] + got["trace_lower_s"] + got["backend_s"]
    cell["say"](
        "set-up, two views: the benchmark's spans build + warmup %.3f s | "
        "the program's records there %.3f s + warm-up steps on the device "
        "%.3f s + unattributed %.3f s; init %.3f + trace/lower %.3f + "
        "backend %.3f = %.3f s, %.3f s as one union (overlap %.3f s: "
        "compiles inside init phases), over the whole of set-up"
        % (got["base_s"], got["covered_s"], got["device_s"],
           got["unattributed_s"], got["init_s"], got["trace_lower_s"],
           got["backend_s"], parts, got["all_s"], parts - got["all_s"]))
    for secs, span, after, before in got["gaps"][:GAPS]:
        cell["say"]("  in no record: %.3f s of %s, after %s and before %s"
                    % (secs, span, after, before))
    return 100.0 * got["unattributed_s"] / got["base_s"]
