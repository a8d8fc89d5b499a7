"""Recompile detector — the classic TPU perf footgun, made loud.

The executor compiles a program once per cache key (program version, feed
shapes/dtypes, fetch names, state set, sharding config — executor.py) and
every later run hits the cache.  A key that keeps changing — ragged batch
sizes, a program rebuilt per step, a fetch list constructed in the loop —
recompiles silently: each miss costs seconds of XLA time and the step loop
never reaches steady state.  The reference had nothing here either (you
found out from conspicuously slow trainers); this detector logs every
compile-cache miss with the DIFF of its key against the previous key of the
same program, counts compiles per program in the StatRegistry
("monitor.compile" / "monitor.recompile"), and warns once when one program
recompiles ``warn_after`` times.

The trainer path (``StepTrainer`` and the ``build_*_trainer``) never reaches
an executor's cache key: what it traces, lowers, compiles or loads is heard
off ``jax.monitoring`` instead, by the process's one ``compile_ledger()``,
together with the phases of set-up that the program marks itself
(``CompileLedger.phase``).  Always on: a listener fires on compile events
only, which jit's cached call path never reaches.

The same ledger keeps what the HOST did once training runs, on the same
clock: a ``call`` record a ``StepTrainer.step`` / ``run_steps``, a ``gc``
record a collection of Python's collector and a ``stall`` record a late
beat of its watch thread; ``CompileLedger.window(t0, t1)`` puts a slow
stretch of a job down to them.
"""

import collections
import contextlib
import gc
import os
import resource
import statistics
import threading
import time
import warnings

from . import memscope as _memscope, trace as _trace
from .registry import default_registry

__all__ = ["RecompileDetector", "RecompileStorm", "CompileLedger",
           "compile_ledger", "union_seconds", "FIRST_CALL"]


class RecompileStorm(RuntimeError):
    """Strict-mode trip: a program recompiled past its budget.  Serving is
    the canonical user (serving/engine.py): every dispatchable shape is
    pre-compiled at server start, so ANY recompile under load is a lost
    latency budget — the detector raises (naming the drifted key
    component) instead of warning.  Carries ``ident`` and ``diff``."""

    def __init__(self, msg, ident=None, diff=()):
        super().__init__(msg)
        self.ident = ident
        self.diff = list(diff)

# bounds for an always-on session: a pathological shape-churn job (the very
# thing the detector exists to catch) must not make the detector itself the
# memory leak — event history is a ring, per-ident state an LRU
_MAX_EVENTS = 1024
_MAX_IDENTS = 4096


class RecompileDetector:
    def __init__(self, registry, timeline=None, warn_after=3, strict=False):
        self.registry = registry
        self.timeline = timeline
        self.warn_after = int(warn_after)
        # strict: once a program's recompiles exceed ``warn_after``, EVERY
        # offending record_compile raises RecompileStorm (no warn-once
        # dedup — each recompile under a strict gate is its own failure).
        # The counters/timeline still record the event first, so the trip
        # leaves evidence behind the exception.
        self.strict = bool(strict)
        self._lock = threading.Lock()
        # ident -> last key parts (insertion-ordered for LRU trimming)
        self._last_parts = collections.OrderedDict()
        self._n_compiles = {}          # ident -> compile count
        self._warned = set()
        self.events = collections.deque(maxlen=_MAX_EVENTS)  # recent events
        self.total_compiles = 0        # lifetime, survives the ring
        self.total_recompiles = 0

    def record_compile(self, ident, parts):
        """Call on a genuine compile-cache miss (never on a hit).

        ident: stable program identity (same program object -> same ident);
        parts: {component_name: comparable value} — the cache key split into
        named components so the diff can say WHAT changed.
        Returns the event dict (also appended to the timeline).
        """
        with self._lock:
            prev = self._last_parts.get(ident)
            n = self._n_compiles.get(ident, 0) + 1
            self._n_compiles[ident] = n
            self._last_parts[ident] = dict(parts)
            self._last_parts.move_to_end(ident)
            while len(self._last_parts) > _MAX_IDENTS:
                old, _ = self._last_parts.popitem(last=False)
                self._n_compiles.pop(old, None)
                self._warned.discard(old)
            recompile = prev is not None
            self.total_compiles += 1
            if recompile:
                self.total_recompiles += 1
            diff = []
            if recompile:
                keys = set(prev) | set(parts)
                diff = sorted(k for k in keys
                              if prev.get(k) != parts.get(k))
            ev = {"ident": ident, "recompile": recompile, "diff": diff,
                  "n_compiles": n}
            self.events.append(ev)
            over_budget = recompile and n - 1 >= self.warn_after
            should_warn = (over_budget and not self.strict
                           and ident not in self._warned)
            if should_warn:
                self._warned.add(ident)
        self.registry.counter("monitor.compile").incr()
        if recompile:
            self.registry.counter("monitor.recompile").incr()
        if self.timeline is not None:
            self.timeline.emit("compile", **ev)
        msg = ("program %r recompiled %d times (last key change: %s) — "
               "each miss pays full XLA compilation; stabilize the feed "
               "shapes/fetch list (pad batches to a bucket) or rebuild the "
               "program outside the step loop" % (ident, n - 1,
                                                  ", ".join(diff) or "?"))
        if self.strict and over_budget:
            # strict is a GATE, not advice: the event above is the
            # evidence, this is the verdict
            raise RecompileStorm(msg, ident=ident, diff=diff)
        if should_warn:
            warnings.warn(msg, stacklevel=3)
        return ev

    def record_warm(self, ident, parts, deserialize_ms=None):
        """A WarmStart disk hit (warm.py): the program did NOT compile —
        deserializing a persisted executable is the whole point — so this
        must never count as compile churn.  The key parts still become the
        ident's baseline so a LATER key drift diffs against them (a warm
        hit followed by ragged shapes is still a named recompile), and the
        timeline records the hit distinctly (``cached="disk"``)."""
        with self._lock:
            self._last_parts[ident] = dict(parts)
            self._last_parts.move_to_end(ident)
            while len(self._last_parts) > _MAX_IDENTS:
                old, _ = self._last_parts.popitem(last=False)
                self._n_compiles.pop(old, None)
                self._warned.discard(old)
            self._n_compiles.setdefault(ident, 0)
            ev = {"ident": ident, "recompile": False, "diff": [],
                  "cached": "disk"}
            if deserialize_ms is not None:
                ev["deserialize_ms"] = round(deserialize_ms, 3)
            self.events.append(ev)
        if self.timeline is not None:
            self.timeline.emit("compile", **ev)
        return ev

    def recompiles(self, ident=None):
        """Total recompile count (first compiles excluded), optionally for
        one program."""
        with self._lock:
            if ident is not None:
                return max(self._n_compiles.get(ident, 0) - 1, 0)
            return self.total_recompiles


# --------------------------------------------------------- compile ledger --

# jax.monitoring's duration events, by what the interval was spent on, each
# with the program's ``fun_name``
_DURATION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # wraps compile_or_get_cached: fires for a cache load as well
    "/jax/core/compile/backend_compile_duration": "backend",
}
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# JAX says "hit" at every load, but "miss" only where it goes on to write
# the cache (never on the CPU, nor under the cache's floor of compile
# seconds): a backend record with no hit before it is a compile, and the
# registry's hits and misses are counted off the records
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": True,
                 "/jax/compilation_cache/cache_misses": False}
# One trace of a step hears a thousand or more jitted jnp functions traced
# inside it, each a record: a benchmark run makes some 5,600 in all.  The
# ring holds three such set-ups, and is otherwise for the process that
# churns shapes for a week.
_MAX_RECORDS = 16384
# the phase around a trainer's first call of a program (parallel/train.py):
# a backend compile of the same name after it has closed, under no phase, is
# a step that compiled again
FIRST_CALL = "first_call"
# The host's records (``call``, ``gc``, ``stall``) have a ring of their own:
# a host-fed job writes a ``call`` a step and Python collects its youngest
# generation many times a second, and neither may push set-up's records out
# of ``records``.  At 22 steps a second the ring holds ten minutes.
_MAX_HOST_RECORDS = 16384
# the watch thread sleeps ``_BEAT_S`` a turn and records a beat that wakes
# more than ``_LATE_S`` after it was due
_BEAT_S, _LATE_S = 0.010, 0.050
WATCH_THREAD = "paddle_tpu.ledger_watch"


def union_seconds(intervals):
    """Seconds covered by ``(t0, t1)`` intervals, each instant once: trace
    events nest (``matmul`` fires inside ``my_step``'s), so a plain sum
    counts twice."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


class CompileLedger:
    """Where a process's set-up went, under the program's own names, on
    ``time.perf_counter()``.  ``records`` holds dicts, oldest first:

    - ``kind`` ``trace`` | ``lower`` | ``backend``: one ``jax.monitoring``
      duration event.  ``name`` is its ``fun_name``, ``t1`` the instant it
      was heard and ``t0 = t1 - duration``, ``thread`` the thread's name,
      ``parent`` the name of the innermost phase open on that thread then
      (or None).  Trace events nest, in one another and in a lowering, and
      every one is a record: a kind's seconds are the UNION of its
      intervals (``union_seconds``), never their sum.  A ``backend`` record
      also says whether the persistent cache served the program
      (``cached``) and, if so, the compile seconds that saved (``saved_s``);
    - ``kind`` ``phase``: a closed ``phase(name, **labels)``, with its
      ``labels`` and, under ``memory``, the device memory's watermark as it
      closed (``memscope.watermark``: ``bytes_in_use``, ``peak_bytes_in_use``,
      ``bytes_reserved``, ``peak_bytes_reserved`` of the fullest local
      device, ``estimated`` where the backend keeps no counters).  The peaks
      only rise, so two records say which stretch raised one.

    ``host_records`` holds what the host did once training runs, in a ring
    of its own (``between(t0, t1, host=True)``, ``window``):

    - ``kind`` ``call``: one ``StepTrainer.step`` / ``run_steps``, enter to
      return (``call``): ``name`` (``<label>.step`` / ``.run_steps``),
      ``thread``, ``cpu_s`` and ``thread_cpu_s`` (the process's and the
      thread's CPU seconds across it: ``time.process_time``,
      ``time.thread_time``) and, from the thread's second call on, ``gap_s``,
      ``gap_cpu_s`` and ``gap_thread_cpu_s``: the same three across the
      stretch since the previous call returned.  A turn (gap and call) of a
      second of wall beside a second of CPU was the process's own work; with
      none, it was blocked or descheduled;
    - ``kind`` ``gc``: one collection of Python's collector (``on_gc``, a
      ``gc.callbacks`` entry): ``generation``, ``collected``, ``thread``;
    - ``kind`` ``stall``: a beat of the watch thread that woke more than
      ``_LATE_S`` late (``beat``): ``t0`` when it was due, ``t1`` when it
      ran, ``cpu_s`` the process's CPU seconds since the beat before,
      ``gc`` whether a collection was open or closed in it, ``switches`` the
      process's involuntary context switches and ``throttled_usec`` the
      growth of its cgroup's throttled time across it (None where no
      ``cpu.stat`` says).  With CPU: the process's own work (a thread that
      kept the interpreter's lock); without CPU and with ``throttled_usec``:
      the host's quota; without either: descheduled, or stopped.

    ``compile_ledger()`` is the process's one, the only one with a watch
    thread and a place in ``gc.callbacks``; a test makes its own and feeds
    it events, collections and beats by hand."""

    def __init__(self, registry, watch=False):
        self.registry = registry
        self.records = collections.deque(maxlen=_MAX_RECORDS)
        self.total_records = 0         # lifetime, survives the ring
        # no lock: a collection can start inside any allocation, one made
        # under ``_lock`` too, and its callback appends here.  An append and
        # ``list(deque)`` are each one step under the interpreter's lock
        self.host_records = collections.deque(maxlen=_MAX_HOST_RECORDS)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._first_called = set()     # programs that had their first call
        self._watch = watch            # start the thread at a first call
        self._watching = False
        self._annotation = None        # jax.profiler.TraceAnnotation
        self._gc_open = None           # start of the collection under way
        self._gc_closed = float("-inf")    # end of the last one
        self._beat = None              # (due, cpu, switches, throttled)
        self._cpu_stat = None          # (fd, key, units a microsecond)

    def _state(self):
        st = self._local
        if not hasattr(st, "stack"):
            st.stack = []              # open phases: [name, last program]
            st.pending = {}            # cache events awaiting their program
            st.returned = None         # the last call's (t1, cpu, own cpu)
        return st

    def _append(self, record):
        with self._lock:
            self.records.append(record)
            self.total_records += 1

    def on_duration(self, event, secs, fun_name=None, **_kw):
        """The ``jax.monitoring`` duration listener."""
        st = self._state()
        if event == _SAVED:
            st.pending["saved_s"] = secs
            return
        kind = _DURATION_KINDS.get(event)
        if kind is None:
            return
        t1 = time.perf_counter()
        record = {"kind": kind, "name": fun_name, "t0": t1 - secs, "t1": t1,
                  "thread": threading.current_thread().name,
                  "parent": st.stack[-1][0] if st.stack else None}
        if kind == "backend":
            pending, st.pending = st.pending, {}
            record["cached"] = pending.get("cached", False)
            record["saved_s"] = pending.get("saved_s", 0.0)
            self.registry.counter(
                "monitor.compile.cache_hits" if record["cached"]
                else "monitor.compile.cache_misses").incr()
            if st.stack:
                # the last one under a first_call phase is the call's own
                # (an eager constant met while tracing compiles before it)
                st.stack[-1][1] = fun_name
            elif fun_name in self._first_called:
                self.registry.counter(
                    "monitor.compile.after_first_call").incr()
        self._append(record)
        self.registry.histogram("monitor.compile.seconds",
                                kind=kind).observe(secs)

    def on_event(self, event, **_kw):
        """The ``jax.monitoring`` event listener: a cache hit says of the
        ``backend`` record that follows it on this thread that the program
        was loaded, not compiled."""
        cached = _CACHE_EVENTS.get(event)
        if cached is not None:
            self._state().pending["cached"] = cached

    @contextlib.contextmanager
    def phase(self, name, **labels):
        """Marks a stretch of set-up on this thread; yields ``labels``, which
        the caller may add to until the phase closes.  Two clock reads, one
        record and, after the second read, one ``memory_stats()`` a local
        device; a monitor session's trace shows it as ``setup.<name>``."""
        st = self._state()
        parent = st.stack[-1][0] if st.stack else None
        frame = [name, None]
        st.stack.append(frame)
        if name == FIRST_CALL and self._watch:
            self.start_watch()
        t0 = time.perf_counter()
        try:
            with _trace.span("setup." + name, **labels):
                yield labels
        finally:
            t1 = time.perf_counter()
            st.stack.pop()
            if name == FIRST_CALL and frame[1] is not None:
                self._first_called.add(frame[1])
            record = {"kind": "phase", "name": name, "t0": t0, "t1": t1,
                      "thread": threading.current_thread().name,
                      "parent": parent, "labels": labels}
            try:
                mark = _memscope.watermark()
            except Exception:
                mark = None
            if mark is not None:
                record["memory"] = mark
            self._append(record)
            self.registry.histogram("monitor.setup.phase_ms",
                                    phase=name).observe((t1 - t0) * 1e3)

    @contextlib.contextmanager
    def call(self, name):
        """One call of a trainer's program on this thread, as a ``call``
        record: two reads of each of three clocks and one append.  A profile
        shows it as a ``jax.profiler.TraceAnnotation`` of the same name (a
        no-op while no profiler session is on), a monitor session's trace as
        a span."""
        if self._annotation is None:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation
        st = self._state()
        t0, cpu0, own0 = (time.perf_counter(), time.process_time(),
                          time.thread_time())
        try:
            with self._annotation(name), _trace.span(name):
                yield
        finally:
            t1, cpu1, own1 = (time.perf_counter(), time.process_time(),
                              time.thread_time())
            last, st.returned = st.returned, (t1, cpu1, own1)
            gap = (None,) * 3 if last is None else (
                t0 - last[0], cpu0 - last[1], own0 - last[2])
            self.host_records.append({
                "kind": "call", "name": name, "t0": t0, "t1": t1,
                "thread": threading.current_thread().name,
                "cpu_s": cpu1 - cpu0, "thread_cpu_s": own1 - own0,
                "gap_s": gap[0], "gap_cpu_s": gap[1],
                "gap_thread_cpu_s": gap[2]})

    def on_gc(self, phase, info):
        """The ``gc.callbacks`` entry: a ``gc`` record a collection.  Costs
        nothing between collections."""
        now = time.perf_counter()
        if phase == "start":
            self._gc_open = now
        elif self._gc_open is not None:
            t0, self._gc_open, self._gc_closed = self._gc_open, None, now
            self.host_records.append({
                "kind": "gc", "generation": info["generation"], "t0": t0,
                "t1": now, "collected": info["collected"],
                "thread": threading.current_thread().name})

    def start_watch(self):
        """Starts the watch thread, once: a daemon that calls ``beat``
        every ``_BEAT_S``."""
        with self._lock:
            if self._watching:
                return
            self._watching = True
            self._cpu_stat = _open_cpu_stat()
            threading.Thread(target=self._watch_loop, name=WATCH_THREAD,
                             daemon=True).start()

    def _watch_loop(self):
        while True:
            time.sleep(_BEAT_S)
            self.beat(time.perf_counter())

    def beat(self, now):
        """One beat of the watch at ``now`` (``time.perf_counter()``): due
        ``_BEAT_S`` after the beat before, and a ``stall`` record if it
        comes more than ``_LATE_S`` after that.  A thread that slept 10 ms
        and woke late was kept off the CPU or off the interpreter's lock,
        and so was every other thread of the process, the one that feeds
        the device among them.  Returns the record, or None."""
        cpu = time.process_time()
        switches = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        throttled = self._throttled_usec()
        last, self._beat = self._beat, (now + _BEAT_S, cpu, switches,
                                        throttled)
        if last is None or now - last[0] <= _LATE_S:
            return None
        record = {"kind": "stall", "t0": last[0], "t1": now,
                  "cpu_s": cpu - last[1],
                  "gc": self._gc_open is not None
                  or self._gc_closed > last[0],
                  "switches": switches - last[2],
                  "throttled_usec": None if None in (throttled, last[3])
                  else throttled - last[3],
                  "thread": threading.current_thread().name}
        self.host_records.append(record)
        return record

    def _throttled_usec(self):
        """Microseconds the process's cgroup has been throttled for, or
        None where no ``cpu.stat`` says."""
        if self._cpu_stat is None:
            return None
        fd, key, per_usec = self._cpu_stat
        try:
            for line in os.pread(fd, 4096, 0).split(b"\n"):
                if line.startswith(key):
                    return int(line.split()[1]) / per_usec
        except (OSError, ValueError, IndexError):
            pass
        return None

    def between(self, t0, t1, host=False):
        """The records that lie inside ``[t0, t1]``, oldest first: set-up's
        (``records``) or, with ``host``, the ``call``, ``gc`` and ``stall``
        records (``host_records``)."""
        if host:
            held = list(self.host_records)
        else:
            with self._lock:
                held = list(self.records)
        return [r for r in held if r["t0"] >= t0 and r["t1"] <= t1]

    def window(self, t0, t1):
        """What the host did in ``[t0, t1]``, a slow stretch of a job or a
        benchmark's measured window, by the ledger's records:

        - ``calls``, ``call_p50_s``, ``call_max_s``: the ``call`` records
          inside it, the median and the longest of their durations (None
          without one);
        - ``compile_s``: the union of the ``trace``, ``lower`` and
          ``backend`` records (a program traced, lowered, compiled or
          loaded while training runs); ``gc_s``: of the ``gc`` records;
          ``stall_s``: of the ``stall`` records OUTSIDE both (a beat is late
          for the length of a collection, which keeps the interpreter's
          lock, and is counted once, as the collection).  A record that
          straddles an end counts for its part inside;
        - ``turn``: the longest turn of a thread, from one call's return to
          the next one's, as ``name``, ``t0``, ``t1``, ``wall_s`` and the
          process's ``cpu_s`` and the thread's ``thread_cpu_s`` beside it
          (None under two calls);
        - ``longest``: per kind (``call``, ``gc``, ``stall``), its three
          longest records that touch the stretch."""
        def touching(records, kinds):
            return [r for r in records if r["kind"] in kinds
                    and r["t1"] > t0 and r["t0"] < t1]

        def clipped(records):
            return [(max(r["t0"], t0), min(r["t1"], t1)) for r in records]

        def seconds(r):
            return r["t1"] - r["t0"]

        with self._lock:
            records = list(self.records)
        host = list(self.host_records)
        of = {k: touching(host, (k,)) for k in ("call", "gc", "stall")}
        calls = [r for r in of["call"] if r["t0"] >= t0 and r["t1"] <= t1]
        busy = clipped(touching(records, _DURATION_KINDS.values()))
        collecting = clipped(of["gc"])
        turns = [r for r in calls if r["gap_s"] is not None
                 and r["t0"] - r["gap_s"] >= t0]
        turn = max(turns, key=lambda r: r["gap_s"] + seconds(r), default=None)
        return {
            "calls": len(calls),
            "call_p50_s": statistics.median(map(seconds, calls))
            if calls else None,
            "call_max_s": max(map(seconds, calls), default=None),
            "compile_s": union_seconds(busy),
            "gc_s": union_seconds(collecting),
            "stall_s": union_seconds(busy + collecting
                                     + clipped(of["stall"]))
            - union_seconds(busy + collecting),
            "turn": turn and {
                "name": turn["name"], "t0": turn["t0"] - turn["gap_s"],
                "t1": turn["t1"], "wall_s": turn["gap_s"] + seconds(turn),
                "cpu_s": turn["gap_cpu_s"] + turn["cpu_s"],
                "thread_cpu_s": turn["gap_thread_cpu_s"]
                + turn["thread_cpu_s"]},
            "longest": {k: sorted(rs, key=seconds, reverse=True)[:3]
                        for k, rs in of.items()}}

    def table(self, records=None):
        """Rows per program and parent phase, costliest first, of
        ``records`` (default: all held): ``name`` (the ``fun_name`` without
        its ``jit(...)``), ``parent``, ``n`` programs built or loaded,
        ``trace_s`` / ``lower_s`` / ``backend_s`` (each the union of its
        intervals), ``compiled`` and ``loaded`` (how many of ``n``)."""
        if records is None:
            with self._lock:
                records = list(self.records)
        groups = {}
        for r in records:
            if r["kind"] in _DURATION_KINDS.values():
                key = (_bare(r["name"]), r["parent"])
                groups.setdefault(key, {}).setdefault(r["kind"], []).append(r)
        rows = []
        for (name, parent), kinds in groups.items():
            built = kinds.get("backend")
            if not built:
                continue         # traced inside another program, or not run
            row = {"name": name, "parent": parent, "n": len(built),
                   "loaded": sum(1 for r in built if r["cached"])}
            row["compiled"] = row["n"] - row["loaded"]
            for kind in ("trace", "lower", "backend"):
                row[kind + "_s"] = union_seconds(
                    (r["t0"], r["t1"]) for r in kinds.get(kind, ()))
            rows.append(row)
        rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"]
                                  + r["backend_s"]))
        return rows


def _open_cpu_stat():
    """``(fd, key, units a microsecond)`` of the ``cpu.stat`` of the
    process's cgroup that counts its throttled time (``throttled_usec``
    under cgroup v2, ``throttled_time`` in ns under v1), or None.  Kept open
    for the life of the process: the watch reads it at every beat."""
    paths = []
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, controllers, path = line.strip().split(":", 2)
                if not controllers:
                    paths.append("/sys/fs/cgroup" + path)
                elif "cpu" in controllers.split(","):
                    paths.append("/sys/fs/cgroup/cpu" + path)
    except (OSError, ValueError):
        pass
    for path in paths + ["/sys/fs/cgroup", "/sys/fs/cgroup/cpu"]:
        try:
            fd = os.open(os.path.join(path, "cpu.stat"), os.O_RDONLY)
        except OSError:
            continue
        text = os.pread(fd, 4096, 0)
        for key, per_usec in ((b"throttled_usec", 1), (b"throttled_time",
                                                       1000)):
            if key in text:
                return fd, key, per_usec
        os.close(fd)
    return None


def _bare(fun_name):
    """``jit(multi)`` (lowering's and the backend's name) -> ``multi`` (the
    trace's)."""
    if fun_name and fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


_ledger = None
_ledger_lock = threading.Lock()


def compile_ledger():
    """The process's ledger, made and registered with ``jax.monitoring`` at
    first use (a listener cannot be taken back, so there is one for good).
    ``compile_cache.place()`` and ``monitor.enable()`` call this before the
    first compile; the phase sites call it as they run."""
    global _ledger
    if _ledger is None:
        with _ledger_lock:
            if _ledger is None:
                import jax.monitoring

                ledger = CompileLedger(default_registry(), watch=True)
                jax.monitoring.register_event_duration_secs_listener(
                    ledger.on_duration)
                jax.monitoring.register_event_listener(ledger.on_event)
                gc.callbacks.append(ledger.on_gc)
                _ledger = ledger
    return _ledger
