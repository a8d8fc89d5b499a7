"""Train driver / set-up: seconds inside the program's ``init_params``,
``init_opt_state`` and ``place`` phases (a staging's ``place`` left out),
as a union: parameters and optimizer state made leaf by leaf, one small
program each, and put on the mesh.  From the start of ``bench.build`` to
the window."""

from ..harness import setup_time


def read(trace, spans, counters, cell):
    got = setup_time.split(spans, cell)
    if got is None:
        return None
    cell["say"]("set-up phases (seconds; self = less what was recorded "
                "inside; programs built or loaded inside):")
    for p in got["phases"]:
        inner = setup_time.recorded_inside(p, got["records"])
        cell["say"]("  %-14s %-13s %9.4f  self %9.4f  programs %3d  %s" % (
            p["name"], p["parent"] or "-", p["t1"] - p["t0"],
            setup_time.self_seconds(p, inner),
            sum(1 for r in inner if r["kind"] == "backend"),
            p["labels"] or ""))
    return got["init_s"]
