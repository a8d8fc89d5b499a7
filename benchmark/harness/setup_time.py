"""Set-up under the program's own names.

The benchmark times set-up from outside (``bench.build``, ``bench.stage``,
``bench.warmup``, ``bench.reference``: the ``setup:`` line).  The program
accounts for it from inside: ``paddle_tpu.monitor.recompile.compile_ledger``
holds, on the same clock as the spans, one record per trace, lowering,
backend compile or cache load that ``jax.monitoring`` reported, and one per
phase the trainers' set-up marks (``init_params``, ``init_opt_state``,
``place``, ``stage_batches``, ``first_call``).  Joined here: the records
from the start of ``bench.build`` to the start of the measured window, less
those inside the benchmark's own checks: ``bench.reference`` (its float32
programs are the benchmark's compiles) and, where a driver has one,
``bench.witness`` (the reference's logits, and a forward of the program that
only the witness calls).

A program without the ledger (an earlier commit) gives no split, and the
readers built on it return nothing.
"""

import statistics

INIT_PHASES = ("init_params", "init_opt_state")
CHECKS = ("bench.reference", "bench.witness")
KINDS = ("trace", "lower", "backend")


def ledger():
    """The program's compile ledger, or None where it has none."""
    try:
        from paddle_tpu.monitor.recompile import compile_ledger
    except ImportError:
        return None
    return compile_ledger()


def _span(spans, name):
    """The first span of that name: (t0, t1), or None."""
    for n, t0, t1, _ in spans.records:
        if n == name:
            return t0, t1
    return None


def _inside(r, span):
    return r["t0"] >= span[0] and r["t1"] <= span[1]


def _at(records):
    return [(r["t0"], r["t1"]) for r in records]


def split(spans, cell):
    """The run's set-up by the program's records, or None without the
    ledger or without a ``bench.build`` span.  Seconds are unions of
    intervals (``union_seconds``): trace events nest, phases hold compiles.

    ``heard``: the events the ledger has heard in the process so far, of
    which ``records`` are those of set-up it holds; ``init_s``:
    ``init_params``, ``init_opt_state`` and the ``place`` phases that are
    not a staging's; ``trace_lower_s``: trace and lower records,
    the part no cache serves; ``backend_s``: backend records (a compile, or
    a cache load and deserialisation); ``compiled`` / ``loaded``: programs
    by whether the persistent cache served them; ``saved_s``: the compile
    seconds the cache says it saved.  ``base_s`` is ``bench.build`` +
    ``bench.warmup``, ``covered_s`` the part of it inside any record,
    ``device_s`` the warm-up's own steps at the window's median step, and
    ``unattributed_s`` what is left, signed: below 0, ``device_s`` took
    away more than the warm-up's steps were.  ``gaps``: the stretches of
    the two spans inside no record, longest first (``uncovered``)."""
    led = ledger()
    build = _span(spans, "bench.build")
    if led is None or build is None:
        return None
    from paddle_tpu.monitor.recompile import union_seconds

    checks = [s for s in (_span(spans, name) for name in CHECKS) if s]
    records = [r for r in led.between(build[0], cell["t0"])
               if not any(_inside(r, s) for s in checks)]
    phases = [r for r in records if r["kind"] == "phase"]
    of = {k: [r for r in records if r["kind"] == k] for k in KINDS}
    init = [r for r in phases if r["name"] in INIT_PHASES
            or (r["name"] == "place" and r["parent"] != "stage_batches")]
    out = {"records": records, "phases": phases, "heard": led.total_records,
           "init_s": union_seconds(_at(init)),
           "trace_lower_s": union_seconds(_at(of["trace"] + of["lower"])),
           "backend_s": union_seconds(_at(of["backend"])),
           "loaded": sum(1 for r in of["backend"] if r["cached"]),
           "saved_s": sum(r["saved_s"] for r in of["backend"]),
           "all_s": union_seconds(_at(init + of["trace"] + of["lower"]
                                      + of["backend"]))}
    out["compiled"] = len(of["backend"]) - out["loaded"]
    base = {n: _span(spans, n) for n in ("bench.build", "bench.warmup")}
    out["gaps"] = sorted((g for n, s in base.items() if s
                          for g in uncovered(records, n, s)), reverse=True)
    out["base_s"] = sum(t1 - t0 for t0, t1 in filter(None, base.values()))
    out["covered_s"] = out["base_s"] - sum(g[0] for g in out["gaps"])
    step_ms = cell.get("step_ms") or [0.0]
    out["device_s"] = _warmup_steps(cell["traffic"]) \
        * statistics.median(step_ms) / 1e3
    out["unattributed_s"] = out["base_s"] - out["covered_s"] \
        - out["device_s"]
    return out


def uncovered(records, name, span):
    """The stretches of ``span`` inside no record, as ``(seconds, name of
    the span, what ended before the stretch, what started after it)``: where
    to look when the records fall short of the span."""
    end, last, out = span[0], "its start", []
    inside = sorted((max(r["t0"], span[0]), min(r["t1"], span[1]),
                     "%s %s" % (r["kind"], r["name"])) for r in records
                    if r["t1"] > span[0] and r["t0"] < span[1])
    for t0, t1, what in inside + [(span[1], span[1], "its end")]:
        if t0 > end:
            out.append((t0 - end, name, last, what))
        if t1 >= end:
            end, last = t1, what
    return out


def _warmup_steps(traffic):
    """Steps the driver's warm-up runs: the scan drivers dispatch every
    staged batch once, the host-fed driver takes two steps."""
    return int(traffic.get("staged_batches", 2))


def recorded_inside(phase, records):
    """What the phase's thread recorded inside it."""
    return [r for r in records if r is not phase
            and r["thread"] == phase["thread"]
            and _inside(r, (phase["t0"], phase["t1"]))]


def self_seconds(phase, inner):
    """A phase's duration less the union of ``inner``, what was recorded
    inside it."""
    from paddle_tpu.monitor.recompile import union_seconds

    return phase["t1"] - phase["t0"] - union_seconds(_at(inner))
