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
"""

import collections
import contextlib
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

    ``compile_ledger()`` is the process's one; a test makes its own and
    feeds it events by hand."""

    def __init__(self, registry):
        self.registry = registry
        self.records = collections.deque(maxlen=_MAX_RECORDS)
        self.total_records = 0         # lifetime, survives the ring
        self._lock = threading.Lock()
        self._local = threading.local()
        self._first_called = set()     # programs that had their first call

    def _state(self):
        st = self._local
        if not hasattr(st, "stack"):
            st.stack = []              # open phases: [name, last program]
            st.pending = {}            # cache events awaiting their program
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

    def between(self, t0, t1):
        """The records that lie inside ``[t0, t1]``, oldest first."""
        with self._lock:
            return [r for r in self.records if r["t0"] >= t0 and r["t1"] <= t1]

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

                ledger = CompileLedger(default_registry())
                jax.monitoring.register_event_duration_secs_listener(
                    ledger.on_duration)
                jax.monitoring.register_event_listener(ledger.on_event)
                _ledger = ledger
    return _ledger
