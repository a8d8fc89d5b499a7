"""The measured window under the program's own names.

The benchmark times the window from outside (``bench.dispatch`` and
``bench.sync`` around the program's call and around its own wait; the
completion marks that ``train_throughput`` and ``window_lost_share`` are
made of).  The program accounts for the HOST inside it: its ledger
(``paddle_tpu.monitor.recompile.compile_ledger``) holds, on the clock of the
spans and of ``cell["t0"]`` / ``cell["t1"]``, a ``call`` record a
``StepTrainer.step`` / ``run_steps``, a ``gc`` record a collection of
Python's collector, a ``stall`` record a late beat of its watch thread, and
the ``trace`` / ``lower`` / ``backend`` records of whatever was built while
the window ran; ``CompileLedger.window(t0, t1)`` sums them.  Six readers
under ``layer_metrics/`` put ``window_lost_share`` down to them.

The profiler runs after the window, so the traced dispatches are another
stretch of the same clock: ``clock_zero`` finds where the trace's clock
starts on it from the benchmark's spans, which are on both, and
``under_gaps`` names the program's record under each of the device's longest
idle gaps.

A program without the records (an earlier commit) gives no account, and the
readers built on it return nothing.
"""

import statistics

from . import setup_time

FLOOR_S = 0.050       # a window that lost less has nothing to attribute
LONGEST = 3           # records of a kind named in the log
COMPILE_KINDS = setup_time.KINDS
PARTS = ("compile_s", "gc_s", "stall_s")    # of ``CompileLedger.window``


def account(cell, t0=None, t1=None):
    """``CompileLedger.window`` over the measured window (or a part of it),
    or None where the program's ledger has no such records."""
    led = setup_time.ledger()
    if led is None or not hasattr(led, "window"):
        return None
    return led.window(cell["t0"] if t0 is None else t0,
                      cell["t1"] if t1 is None else t1)


def ms(seconds):
    return 1e3 * seconds


def describe(record, origin):
    """One record as the log shows it, its instant in ms after ``origin``
    (the window's ``t0``; the trace's zero in the traced part)."""
    kind = record["kind"]
    where = "+%.3f ms %.3f ms long" % (ms(record["t0"] - origin),
                                       ms(record["t1"] - record["t0"]))
    if kind == "call":
        return "call %s %s (process CPU %.3f ms, thread CPU %.3f ms)" % (
            record["name"], where, ms(record["cpu_s"]),
            ms(record["thread_cpu_s"]))
    if kind == "gc":
        return "gc generation %d %s (%d collected, on %s)" % (
            record["generation"], where, record["collected"],
            record["thread"])
    if kind == "stall":
        throttled = record["throttled_usec"]
        return ("stall %s (process CPU %.3f ms, %d involuntary switches, "
                "throttled %s%s)" % (
                    where, ms(record["cpu_s"]), record["switches"],
                    "not known" if throttled is None
                    else "%.3f ms" % (throttled / 1e3),
                    ", a collection inside" if record["gc"] else ""))
    return "%s %s %s" % (kind, record["name"], where)


def say_longest(cell, got, kind):
    for r in got["longest"][kind]:
        cell["say"]("  " + describe(r, cell["t0"]))


def compile_records(cell):
    """The ledger's ``trace``, ``lower`` and ``backend`` records that touch
    the window, longest first."""
    led = setup_time.ledger()
    held = led.between(float("-inf"), float("inf"))
    return sorted((r for r in held if r["kind"] in COMPILE_KINDS
                   and r["t1"] > cell["t0"] and r["t0"] < cell["t1"]),
                  key=lambda r: r["t0"] - r["t1"])


def lost(cell):
    """The window's lost seconds and its start, from what the harness hands
    every reader, or None where it hands too little:

    - ``lost_s``: ``window_s`` - units / ``train_throughput``, which is
      ``window_lost_share`` of ``window_s``;
    - ``first_s``: the window less the stretch its step samples span (they
      lie between the completion marks, ``steps a mark`` steps each): from
      ``t0`` to the first completion and, host-fed, the drain after the
      last;
    - ``start_s``: ``first_s`` less one median mark's steps: what the start
      on an idle device and an empty pipe cost over a dispatch in flight."""
    samples = cell.get("step_ms")
    if not samples or not cell.get("throughput") \
            or not cell.get("window_rate"):
        return None
    per_mark = int(cell["traffic"].get("staged_batches", 1))
    first_s = cell["window_s"] - sum(samples) * per_mark / 1e3
    return {"lost_s": cell["window_s"]
            * (1.0 - cell["window_rate"] / cell["throughput"]),
            "first_s": first_s,
            "start_s": first_s
            - statistics.median(samples) * per_mark / 1e3}


def unattributed(cell):
    """``lost`` and what the records leave of it.  ``start`` and ``later``
    are the account before and after the first completion: what lies before
    it is inside ``start_s`` already and is counted once.  ``after_s`` is
    what the window lost after the start (``lost_s`` - ``start_s``, signed),
    ``explained_s`` the later compile, collection and stall seconds as far
    as ``after_s`` goes (a host that stalls behind a dispatch in flight
    loses the device nothing: its seconds are on the log and explain no
    more than was lost), ``left_s`` the rest.  None without the records or
    without ``lost``."""
    got = lost(cell)
    if got is None:
        return None
    edge = min(cell["t0"] + max(got["first_s"], 0.0), cell["t1"])
    got["start"] = account(cell, t1=edge)
    got["later"] = account(cell, t0=edge)
    if got["later"] is None:
        return None
    got["after_s"] = got["lost_s"] - got["start_s"]
    got["explained_s"] = min(sum(got["later"][k] for k in PARTS),
                             max(got["after_s"], 0.0))
    got["left_s"] = got["after_s"] - got["explained_s"]
    return got


# -- the traced part on the window's clock ------------------------------------

def clock_zero(spans, trace):
    """``(zero, pairs, spread_s)``: the reading of ``time.perf_counter()``
    at the zero of the trace's clock, as the median over the benchmark's
    spans that are in the trace and in ``spans`` both of host start less
    traced start; how many pairs; the distance between the extreme ones.
    The traced spans of a name are consecutive spans of the run: they are
    laid on the run's at the shift at which the durations agree best, the
    latest of equals (the profiler runs last).  None without a pair.  A
    trace that could hold no annotation carries ``tracing.anchored``'s
    spans, which are the host's own less one zero: the pairs then agree
    exactly and give that zero back."""
    offsets = []
    for name in sorted({n for n, _, _ in trace.host_spans}):
        traced = sorted((a, b) for n, a, b in trace.host_spans if n == name)
        mine = sorted((t0, t1) for n, t0, t1, _ in spans.records if n == name)
        shifts = range(len(mine) - len(traced), -1, -1)
        if not shifts:
            continue

        def miss(j):
            return sum(abs((b - a) / 1e9 - (mine[j + i][1] - mine[j + i][0]))
                       for i, (a, b) in enumerate(traced))

        j = min(shifts, key=miss)
        offsets += [mine[j + i][0] - a / 1e9
                    for i, (a, _) in enumerate(traced)]
    if not offsets:
        return None
    return statistics.median(offsets), len(offsets), \
        max(offsets) - min(offsets)


def under_gaps(trace, spans, k=5):
    """The device's ``k`` longest idle gaps of the traced part, in
    ``top_gaps``'s order, each as ``(seconds, ns from the trace's zero, the
    benchmark's span over most of it, the program's record over most of it
    or None)``; with them the zero they were laid by (``clock_zero``).  None
    where the trace has no span to find the zero by or the program no
    records."""
    led = setup_time.ledger()
    zero = clock_zero(spans, trace)
    if zero is None or led is None or not hasattr(led, "window"):
        return None
    records = led.between(zero[0], float("inf"), host=True)
    gaps = sorted(((hi - lo, lo, hi) for d in trace.devices
                   for lo, hi in d["gaps"]), reverse=True)[:k]
    out = []
    for (span, _), (dur, lo, hi) in zip(trace.top_gaps(k), gaps):
        best, cover = None, 0.0
        for r in records:
            c = min((r["t1"] - zero[0]) * 1e9, hi) \
                - max((r["t0"] - zero[0]) * 1e9, lo)
            if c > cover:
                best, cover = r, c
        out.append((dur / 1e9, lo, span, best))
    return zero, out
