"""Pipelined device feed + bounded async dispatch (the latency-hiding layer).

Parity surface: operators/reader/buffered_reader.cc — the reference hides
host input cost behind device compute with a double-buffered reader whose
worker threads stage the NEXT batch's tensors (and start their host→device
copies) while the current batch trains.  Here the same discipline is a
single reusable stage:

- ``DeviceFeedPipe`` — a bounded background thread that pulls raw feed
  dicts from a source iterator, runs the feed conversion +
  ``jax.device_put`` / ``shard_feed`` OFF the training thread, and hands
  device-resident batches to the consumer in source order; each take
  announces the NEXT staged batch to the HostPS prefetch hooks
  (hostps/service.py), one batch ahead.  While step k runs on-device,
  batch k+1 converts and its transfer is in flight — the training
  thread's per-step feed cost collapses to a queue pop.
- ``InFlightWindow`` — the depth governor for the OTHER side of the step:
  async dispatch with lazy fetches lets the host run ahead of the device;
  the window bounds outstanding dispatches to K (default 2, donation-safe:
  it only ever waits on step OUTPUTS, never on donated input buffers) so
  host-ahead stays bounded and dispatch-queue growth can't mask a slow
  device.

Both stages export their health through the monitor registry when a session
is active (``monitor.pipe.*`` gauges/histograms and per-batch ``pipe``
timeline events), so the step timeline shows where time hides: feed_stall_ms
(consumer waited on the pipe — input bound), put_wait_ms (producer waited on
the consumer — device bound, the healthy state), overlap_ms (conversion time
the pipe hid behind compute), fetch_wait_ms (governor waits).

Worker exceptions propagate to the training thread with the ORIGINAL
traceback (the worker frame included), never as a bare queue timeout or a
spurious StopIteration.
"""

import os
import queue as _queue
import threading
import time

from .ft import chaos as _chaos
from .monitor import memscope as _memscope
from .monitor import trace as _trace

__all__ = ["DeviceFeedPipe", "InFlightWindow", "make_feed_convert",
           "pipe_enabled", "default_depth", "default_inflight"]


def pipe_enabled(default=True):
    """PADDLE_TPU_FEED_PIPE=0 disables the background feed stage globally
    (the A/B escape hatch; bench.py PADDLE_TPU_BENCH_PIPE=0 rides on it)."""
    v = os.environ.get("PADDLE_TPU_FEED_PIPE")
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "off")


def default_depth():
    """Pipe capacity (staged batches) — PADDLE_TPU_FEED_PIPE_DEPTH, min 2
    (a 1-deep pipe cannot overlap: the producer would always hand off
    synchronously)."""
    try:
        return max(int(os.environ.get("PADDLE_TPU_FEED_PIPE_DEPTH", "2")), 2)
    except ValueError:
        return 2


def default_inflight():
    """Outstanding-dispatch bound — PADDLE_TPU_MAX_INFLIGHT, default 2."""
    try:
        return max(int(os.environ.get("PADDLE_TPU_MAX_INFLIGHT", "2")), 1)
    except ValueError:
        return 2


def make_feed_convert(dtype_of, placer):
    """THE staging-conversion rule, shared by every pipe feeder
    (Executor.feed_converter, DataLoader's worker): coerce each feed value
    to its CANONICAL declared dtype, then hand the dict to ``placer`` to
    start the device transfer.  ``dtype_of(name)`` returns the canonical
    numpy dtype or None (undeclared names pass through); ``placer(dict)``
    is ``shard_feed`` on a mesh or a per-value ``jax.device_put``.  Keeping
    one implementation keeps it in lockstep with Executor.run's
    jax.Array passthrough check — a staged array the check rejects would
    silently round-trip through host again."""
    import jax
    import numpy as np

    def convert(feed):
        if not isinstance(feed, dict):
            return feed
        out = {}
        for k, v in feed.items():
            dt = dtype_of(k)
            if isinstance(v, jax.Array) and (dt is None or v.dtype == dt):
                out[k] = v
                continue
            out[k] = np.asarray(v, dtype=dt)
        return placer(out)

    return convert


def _staged_arrays(pipe):
    """The device arrays currently STAGED in a pipe's queue — the MemScope
    ``feed_pipe`` owner (batches whose host->device copy started but whose
    step has not consumed them).  Snapshot-read, never locked: attribution
    is a sampler, a torn view costs one batch of accuracy at worst."""
    out = []
    try:
        entries = list(pipe._q.queue)
    except Exception:
        return out
    for e in entries:
        if not (isinstance(e, tuple) and len(e) == 4):
            continue
        item = e[1]
        if isinstance(item, dict):
            out.extend(v for v in item.values() if hasattr(v, "nbytes"))
    return out


def _registry():
    """The monitor registry when a session is active, else None — every
    stat write below is gated on this so the disabled path stays one
    attribute read (the monitor's hot-path contract)."""
    from . import monitor

    mon = monitor.active()
    return None if mon is None else mon


class DeviceFeedPipe:
    """Bounded background feed stage over a batch iterator.

    ``convert`` runs on the worker thread (numpy coercion, device_put,
    shard_feed); ``notify`` fires with the RAW host batch of the NEXT
    item each time the consumer takes one — exactly ONE batch ahead, the
    HostPS prefetch contract (`hostps/service.py` keeps two pending pull
    slots sized for one-ahead announcements; announcing from the worker
    as it converts would run `depth+1` batches ahead and evict the
    next-to-consume prefetch every step).  Iterate the pipe like the
    source; ``close()`` (or abandoning the iterator) shuts the worker
    down without wedging it on a full queue.
    """

    _SENTINEL = object()

    def __init__(self, source, convert=None, notify=None, depth=None,
                 name="feed_pipe"):
        self._source = source
        self._convert = convert
        self._notify = notify
        self.depth = depth if depth and depth >= 2 else default_depth()
        self.name = name
        self._q = _queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._err = []
        self._seq = 0
        # one-ahead announcement state: batch s is announced exactly when
        # batch s-1 has been TAKEN and batch s is STAGED, whichever side
        # completes the condition last (consumer take or worker put) —
        # seq 0 is never announced (it is consumed immediately)
        self._ann_lock = threading.Lock()
        self._announced = 0            # highest seq handed to notify()
        self._taken = -1               # highest seq the consumer took
        self._last_ret = None          # perf_counter of the previous get()
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name=name)
        self._started = False
        # MemScope owner registration (weakref — dies with the pipe): the
        # staged batches this pipe holds classify as "feed_pipe" in the
        # live-buffer attribution instead of unattributed
        _memscope.track("feed_pipe", self, _staged_arrays)

    # -- producer ----------------------------------------------------------
    def _put(self, item):
        """Blocking put that observes close(): a consumer that abandoned the
        iterator must not leave the worker wedged on a full queue."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except _queue.Full:
                continue
        return False

    def _worker(self):
        seq = 0
        try:
            for raw in self._source:
                if self._stop.is_set():
                    return
                # chaos drill point: a worker-thread death here must reach
                # the training thread as THIS exception with THIS traceback
                # (ft/chaos.py; disarmed it is a dict miss)
                _chaos.maybe_fire("feed_worker")
                t0 = time.perf_counter()
                with _trace.span("pipe.convert", seq=seq):
                    item = raw if self._convert is None else self._convert(raw)
                convert_ms = (time.perf_counter() - t0) * 1e3
                t1 = time.perf_counter()
                # raw rides along only when someone will announce it (the
                # consumer's one-ahead notify wants host numpy, pre-convert)
                entry = (seq, item, convert_ms,
                         raw if self._notify is not None else None)
                seq += 1
                with _trace.span("pipe.put_wait"):
                    ok = self._put(entry)
                if not ok:
                    return
                # the consumer may already be waiting on this batch's
                # predecessor's successor (empty-queue take): catch up
                self._maybe_announce(entry[0], entry[3])
                put_wait_ms = (time.perf_counter() - t1) * 1e3
                mon = _registry()
                if mon is not None:
                    mon.registry.histogram(
                        "monitor.pipe.put_wait_ms").observe(put_wait_ms)
        except BaseException as e:       # delivered in order to the consumer
            self._err.append(e)
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(self._SENTINEL, timeout=0.2)
                    break
                except _queue.Full:
                    continue

    # -- one-ahead announcement --------------------------------------------
    def _maybe_announce(self, seq, raw):
        """Announce batch ``seq`` iff it is at most one past the newest
        taken batch and not yet announced — called from the consumer (the
        just-taken entry, then the peeked head) AND from the worker (after
        a put, in case the consumer outran the queue).  The ``<=`` makes a
        racy miss self-heal: if the consumer took k before anyone announced
        it, the take announces it late (the pull still overlaps the step's
        own dispatch) instead of dropping it.  Never more than one ahead —
        the hostps pending slots are sized for exactly that."""
        if raw is None or self._notify is None:
            return
        with self._ann_lock:
            if seq > self._taken + 1 or seq <= self._announced:
                return
            self._announced = seq
        self._notify(raw)

    def _announce_next(self):
        try:
            nxt = self._q.queue[0]     # CPython deque peek: GIL-atomic
        except IndexError:
            return
        if nxt is self._SENTINEL:
            return
        seq, _item, _ms, raw = nxt
        self._maybe_announce(seq, raw)

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        try:
            while True:
                item = self._get()
                if item is self._SENTINEL:
                    break
                yield item
        finally:
            self.close()
        self._reraise()

    def _get(self):
        if not self._started:
            self._started = True
            self._thread.start()
        t0 = time.perf_counter()
        with _trace.span("pipe.take"):
            got = self._q.get()
        now = time.perf_counter()
        if got is self._SENTINEL:
            return self._SENTINEL
        seq, item, convert_ms, raw = got
        if self._notify is not None:
            with self._ann_lock:
                self._taken = seq
            self._maybe_announce(seq, raw)   # catch-up if the early fire lost
            self._announce_next()
        stall_ms = (now - t0) * 1e3
        gap_ms = None if self._last_ret is None else (now - self._last_ret) * 1e3
        self._last_ret = now
        self._seq += 1
        mon = _registry()
        if mon is not None:
            depth = self._q.qsize()
            overlap_ms = max(convert_ms - stall_ms, 0.0)
            reg = mon.registry
            reg.counter("monitor.pipe.batches").incr()
            reg.gauge("monitor.pipe.depth").set(depth)
            reg.histogram("monitor.pipe.feed_stall_ms").observe(stall_ms)
            # FleetScope phase ledger: the consumer (training thread)
            # waited this long on the pipe — input-bound time
            mon.phase_add("feed_stall", stall_ms)
            reg.histogram("monitor.pipe.overlap_ms").observe(overlap_ms)
            ev = {"seq": self._seq - 1, "stall_ms": round(stall_ms, 4),
                  "convert_ms": round(convert_ms, 4),
                  "overlap_ms": round(overlap_ms, 4), "depth": depth}
            if gap_ms is not None:
                # consumer-side wall time since the previous batch left the
                # pipe: the feed-stall fraction's denominator
                # (scripts/trace_summary.py --max-feed-stall-frac)
                ev["gap_ms"] = round(gap_ms, 4)
            mon.timeline.emit("pipe", **ev)
        return item

    def _reraise(self):
        if self._err:
            e = self._err[0]
            # the exception object still carries the worker-thread frames;
            # re-raising it here extends — not replaces — that traceback, so
            # the training thread sees the generator's real crash site
            raise e

    def close(self):
        """Stop the worker and drain the queue so a producer blocked on a
        full queue can observe the stop and exit."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass


class InFlightWindow:
    """Bounds outstanding async dispatches to ``k`` steps.

    ``admit(token)`` enqueues a step OUTPUT (fetch list, a state leaf —
    anything ``jax.block_until_ready`` accepts); once more than ``k`` tokens
    are outstanding the oldest is waited on.  Waiting on outputs only is
    what makes the window donation-safe: donated input buffers are consumed
    at dispatch and never touched again, and an output becoming ready
    implies its whole step (including everything that consumed the donated
    buffers) retired.  The wait cost lands in ``monitor.pipe.fetch_wait_ms``
    — nonzero means the host reached the window bound, i.e. dispatch runs
    ahead of the device (the intended steady state).
    """

    def __init__(self, k=None):
        self.k = k if k is not None else default_inflight()
        self._window = []

    def admit(self, token):
        self._window.append(token)
        while len(self._window) > self.k:
            self._wait(self._window.pop(0))

    def _wait(self, token):
        import jax

        t0 = time.perf_counter()
        try:
            with _trace.span("inflight.wait"):
                jax.block_until_ready(token)
        except Exception as e:           # noqa: BLE001 — filtered below
            # a token whose buffer a LATER dispatch consumed by donation
            # (caller admitted a state leaf instead of a dedicated sync
            # token): that later dispatch subsumes this step's ordering, so
            # skipping the wait keeps the bound loose by one step at worst
            if "deleted" not in str(e) and "donated" not in str(e):
                raise
            return
        mon = _registry()
        if mon is not None:
            wait_ms = (time.perf_counter() - t0) * 1e3
            mon.registry.histogram("monitor.pipe.fetch_wait_ms").observe(
                wait_ms)
            # FleetScope phase ledger: window-bound wait on a step OUTPUT
            # (host ran ahead of the device — the healthy steady state)
            mon.phase_add("fetch", wait_ms)

    def drain(self):
        """Wait for every outstanding dispatch (end-of-run barrier, so run
        wall times measure completed work, not queued work)."""
        while self._window:
            self._wait(self._window.pop(0))

    def __len__(self):
        return len(self._window)
