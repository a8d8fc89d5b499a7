"""Device memory under the program's own names.

The benchmark reads ``peak_hbm_gb`` from outside: the allocator's two peaks
on the fullest chip, added (``harness/device.py``).  The program accounts
for it from inside (``paddle_tpu.monitor.memscope``):

- the LEDGER of the program the window ran (``trainer_ledgers``: the
  compiler's argument / output / alias / temp / generated-code bytes of
  ``<label>.run_steps``, or ``<label>.step`` in a host-fed cell), its
  temporaries by the buffer assignment (``temp_held_bytes``) and its NEED,
  what one chip must hold to run it (``need_bytes``);
- the live bytes by OWNER on the fullest chip (``attribution``: ``params``,
  ``opt_state``, ``running``, ``staged_batches``, ``feed_pipe``, and what no
  owner holds), with the loaded executables' code beside them
  (``loaded_code_bytes``, as the owner ``program_code``: the allocator
  counts it in ``bytes_in_use``);
- the WATERMARKS the compile ledger's phases took as they closed, on the
  clock of the benchmark's spans, and one taken here, after the window:
  which stretch of the run raised each of the two peaks.

Asked once a run, after the window, whichever reader asks first; printed
then.  A program without these (an earlier commit) gives no account, and
the readers built on it return nothing.
"""

import time
import weakref

GB = 1e9
STATE = ("params", "opt_state", "running")
BATCHES = ("staged_batches", "feed_pipe")
CODE = "program_code"
# the benchmark's stretches of a run, in the order they may hold a raise
STRETCHES = ("bench.build", "bench.stage", "bench.witness", "bench.warmup",
             "bench.reference")
PEAKS = ("peak_bytes_in_use", "peak_bytes_reserved")
LARGEST = 10

_accounts = weakref.WeakKeyDictionary()        # Spans -> account, or None


def account(spans, cell):
    """The run's account, or None without the program's part."""
    if spans not in _accounts:
        _accounts[spans] = _ask(spans, cell)
    return _accounts[spans]


def program_of(ledgers, cell):
    """The label of the program the window ran: the scan over the staged
    batches where the traffic stages any, else the one step."""
    kind = ".run_steps" if "staged_batches" in cell["traffic"] else ".step"
    return next((label for label in ledgers if label.endswith(kind)), None)


def _ask(spans, cell):
    try:
        from paddle_tpu.monitor import memscope
    except ImportError:
        return None
    if not hasattr(memscope, "trainer_ledgers"):
        return None
    import jax

    t_asked = time.perf_counter()
    devices = jax.devices()[:cell["chips"]]
    mark = memscope.watermark(devices)           # before anything is asked
    if mark is None:
        return None
    owners = dict(memscope.attribution()["device_owners"].get(mark["device"],
                                                              {}))
    # the loaded executables' code stands in bytes_in_use beside the arrays
    owners[CODE] = memscope.loaded_code_bytes().get(mark["device"], 0)
    ledgers = memscope.trainer_ledgers()
    label = program_of(ledgers, cell)
    if label is None:
        return None
    largest = memscope.largest_values(label, LARGEST)
    after = memscope.watermark(devices)
    got = reduce(label, ledgers[label], memscope.need_bytes(ledgers[label]),
                 mark, owners, _phase_marks(spans), spans.records, cell)
    got["temp_bytes"] = memscope.temp_held_bytes(ledgers[label])
    got["largest"] = largest
    got["in_window"] = _records_in_window(cell)
    got["seconds"] = time.perf_counter() - t_asked
    got["moved"] = {f: after[f] - mark[f] for f in PEAKS if after[f] != mark[f]}
    _say(cell["say"], got, memscope.need_line(label, ledgers[label]))
    return got


def _phase_marks(spans):
    """``[(t1, phase name, watermark)]`` of the compile ledger's phases
    from the first of the run's spans on."""
    from . import setup_time

    led = setup_time.ledger()
    start = min((t0 for _, t0, _, _ in spans.records), default=0.0)
    return [(r["t1"], r["name"], r["memory"]) for r in (led.records if led
                                                        else ())
            if r["kind"] == "phase" and "memory" in r and r["t0"] >= start]


def _records_in_window(cell):
    """``{kind: count}`` of the compile ledger's records inside the
    measured window: a phase there (and so a watermark's
    ``memory_stats()``), a lowering or a compile would each be one.  (The
    first dispatch on a state that a step RETURNED traces ``multi`` once
    more and finds its executable: a ``trace`` record, the program's.)"""
    from . import setup_time

    led = setup_time.ledger()
    kinds = {}
    for r in led.between(cell["t0"], cell["t1"]) if led else ():
        kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
    return kinds


def reduce(label, ledger, need, mark, owners, phase_marks, span_records,
           cell):
    """The account's numbers from what the program gave: no device is
    asked here, so a test hands it a synthetic run."""
    owned = sum(b for o, b in owners.items() if o != "unattributed")
    marks = sorted(phase_marks, key=lambda m: m[0]) \
        + [(time.perf_counter(), "after the window", mark)]
    stretches = [(n, t0, t1) for n, t0, t1, _ in span_records
                 if n in STRETCHES] + [("the window", cell["t0"], cell["t1"])]
    got = {"label": label, "ledger": ledger, "need_bytes": need,
           "device": mark["device"], "estimated": bool(mark.get("estimated")),
           "owners": owners, "in_use": mark["bytes_in_use"], "owned": owned,
           "peak_bytes": mark["peak_bytes_in_use"]
           + mark["peak_bytes_reserved"],
           "marks": marks, "stretches": stretches,
           "raised": {f: last_raise(marks, stretches, f) for f in PEAKS}}
    return got


def last_raise(marks, stretches, field):
    """Where ``field`` reached the value it ends at: ``(bytes it rose by,
    the mark before, the mark at which it stood there, the benchmark's
    stretches that the time between the two meets)``; None where it never
    rose."""
    before = (None, "the process's start", {field: 0})
    found = None
    for m in marks:
        if m[2][field] > before[2][field]:
            found = (m[2][field] - before[2][field], before, m)
        before = m
    if found is None:
        return None
    rose, a, b = found
    t0 = a[0] if a[0] is not None else float("-inf")
    return (rose, a[1], b[1],
            [n for n, s0, s1 in stretches if s1 > t0 and s0 < b[0]])


def _say(say, got, need_line):
    say("memory account: program %s on %s, asked in %.3f s%s"
        % (got["label"], got["device"], got["seconds"],
           " (ESTIMATED from live arrays: the backend keeps no counters)"
           if got["estimated"] else ""))
    say("  " + need_line)
    say("  live bytes by owner there (GB): %s; owned %.6f of bytes_in_use "
        "%.6f" % (", ".join("%s %.6f" % (o, b / GB) for o, b in sorted(
            got["owners"].items(), key=lambda kv: -kv[1])),
            got["owned"] / GB, got["in_use"] / GB))
    zero = got["stretches"][0][1] if got["stretches"] else got["marks"][0][0]
    say("  watermarks (GB), seconds from the first span: in use, its peak, "
        "reserved, its peak; the stretch each was taken in")
    for t, name, m in got["marks"]:
        say("    %9.3f  %-16s %10.6f %10.6f %10.6f %10.6f  %s" % (
            t - zero, name, m["bytes_in_use"] / GB,
            m["peak_bytes_in_use"] / GB, m["bytes_reserved"] / GB,
            m["peak_bytes_reserved"] / GB,
            ", ".join(n for n, t0, t1 in got["stretches"] if t0 <= t <= t1)))
    for field, r in got["raised"].items():
        if r is not None:
            say("  %s last rose between %s and %s, by %.6f GB: %s"
                % (field, r[1], r[2], r[0] / GB,
                   ", ".join(r[3]) or "outside the benchmark's spans"))
    if not got["estimated"]:
        say("  the two peaks together %.6f GB = need %.6f + %.6f that is "
            "not the step" % (got["peak_bytes"] / GB, got["need_bytes"] / GB,
                              (got["peak_bytes"] - got["need_bytes"]) / GB))
    say("  inside the window the compile ledger holds %s: %d phases (so no "
        "watermark), %d lowerings, %d compiles or loads; asking moved %s"
        % (got["in_window"] or "no record", got["in_window"].get("phase", 0),
           got["in_window"].get("lower", 0),
           got["in_window"].get("backend", 0), got["moved"] or "no peak"))
    if got["largest"]:
        say("  largest values of %s by its compiled text (no liveness: "
            "candidates for the peak, not the peak):" % got["label"])
    for v in got["largest"]:
        say("    %10.6f GB  %-28s %-24s %s %s" % (
            v["bytes"] / GB, v["shape"], v["instruction"], v["phase"] or "-",
            v["scope"] or "-"))
