"""MemScope: full-stack memory attribution.

Parity: the reference dedicates a layer to memory (``paddle/fluid/memory/``
— AllocatorFacade stats, BuddyAllocator watermarks — plus the profiler's
memory events and the eager-deletion/memory_optimize passes).  Here XLA owns
allocation, so the questions move up a level; this module answers the three
a production OOM asks:

1. **Which program needed the bytes** — a per-compiled-program memory
   ledger (``Compiled.memory_analysis()``: argument / output / temp /
   generated-code bytes) recorded at every executor compile — cold,
   process-cache adoption, or warm disk hit — into
   ``monitor.mem.program.*{program=}`` gauges and ``mem_program`` timeline
   events, ident-joined to step events exactly like the PR-4 cost events.

2. **Who was holding the rest** — owner-tagged live-buffer attribution:
   subsystems register the arrays they hold (executor scope state, HotRow
   cache slots, feed-pipe staged batches, TrainLoop state, warm
   donation-free twins' pinned first-run buffers, plus ad-hoc
   ``register_owner`` providers) and the periodic memory sample classifies
   ``jax.live_arrays()`` by owner per device with an explicit
   ``unattributed`` remainder — alongside host-side accounting (process
   RSS, HostPS table resident bytes, ShardPS wire replay logs).

3. **Could we have known before dispatch** — the headroom predictor: at
   every compile the program's temp+output requirement is compared against
   ``bytes_limit - bytes_in_use`` per device; a predicted shortfall emits a
   ``mem_headroom`` warning event + ``monitor.mem.predicted_oom`` counter
   BEFORE the dispatch that would die, and the opt-in refuse mode
   (``PADDLE_TPU_MEMSCOPE_REFUSE=1`` / ``configure(refuse=True)``) raises
   ``MemoryBudgetError`` instead of dispatching — the future serving
   admission gate.

When the allocator reports no stats (the CPU backend), a configured
``bytes_limit`` (``configure()`` / ``PADDLE_TPU_MEMSCOPE_LIMIT``) still
arms the predictor: ``bytes_in_use`` falls back to the summed live-array
bytes per device — the framework-visible lower bound (flagged
``estimated``), which is exactly what the deterministic ``oom_step`` drill
exercises off-TPU.

The trainer path (``StepTrainer`` on ``make_train_step``, which never meets
an executor) has the same three parts, each read when asked and never in a
step: ``trainer_ledgers()`` (the ledgers of the programs the trainers
dispatched, off the executables ``monitor/devscope.py`` keeps, and
``need_bytes``: what one chip must hold to run one), ``track_state`` /
``track_arrays`` (the owners ``params``, ``opt_state``, ``running``,
``staged_batches``) and ``watermark()`` (the allocator's four counters on the
fullest device, which the compile ledger's phases record as they close);
``largest_values`` names what is large in a step by the program's scopes,
and ``loaded_code_bytes`` the code of the loaded executables, which the
allocator counts in ``bytes_in_use`` beside the arrays.

An actual RESOURCE_EXHAUSTED (or the injected ``oom_step`` chaos fault) is
caught at the executor dispatch and the TrainLoop and turned into a flight
postmortem ``mem_oom`` section: the failing program's ledger, the headroom
math, the top-K live owners, and the watermark tail — ``note_oom`` rides
``flight.dump(extra=)`` so the one-dump-per-exception contract holds.
"""

import os
import threading
import warnings
import weakref

__all__ = [
    "MemoryBudgetError", "InjectedOOMError",
    "configure", "reset", "refuse_enabled",
    "register_owner", "unregister_owner", "track", "track_state",
    "track_arrays", "attribution", "headroom", "host_accounting",
    "min_device_bytes_limit", "watermark", "WATERMARK_FIELDS",
    "loaded_code_bytes",
    "program_ledger", "record_program", "ledgers", "model_bytes",
    "temp_held_bytes", "need_bytes", "need_line", "trainer_ledgers",
    "largest_values",
    "predict_dispatch",
    "is_resource_exhausted", "oom_extra", "note_oom",
]


class MemoryBudgetError(RuntimeError):
    """Refuse-mode admission: the predictor says this program's temp+output
    requirement exceeds the device headroom — refused BEFORE dispatch."""


class InjectedOOMError(RuntimeError):
    """The deterministic ``oom_step`` chaos fault (ft/chaos.py): a synthetic
    RESOURCE_EXHAUSTED raised at the dispatch boundary, so the whole OOM
    postmortem path is drillable on a backend that cannot really OOM."""


_LOCK = threading.Lock()

# configured overrides: bytes_limit arms the predictor on backends without
# allocator stats; refuse turns the predicted-OOM warning into an admission
# refusal (MemoryBudgetError)
_CONFIG = {"bytes_limit": None, "refuse": None}


def configure(bytes_limit=None, refuse=None):
    """Override the per-device byte limit (None keeps the backend's own
    ``bytes_limit``) and/or the refuse mode.  Tests and the OOM drill use
    the limit override; serving admission uses refuse."""
    with _LOCK:
        if bytes_limit is not None:
            _CONFIG["bytes_limit"] = int(bytes_limit)
        if refuse is not None:
            _CONFIG["refuse"] = bool(refuse)


def _configured_limit():
    with _LOCK:
        v = _CONFIG["bytes_limit"]
    if v is not None:
        return v
    env = os.environ.get("PADDLE_TPU_MEMSCOPE_LIMIT", "").strip()
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    return None


def refuse_enabled():
    with _LOCK:
        v = _CONFIG["refuse"]
    if v is not None:
        return v
    return os.environ.get("PADDLE_TPU_MEMSCOPE_REFUSE", "").strip() in (
        "1", "true", "on")


def reset():
    """Drop every registration / ledger / config override (test isolation)."""
    with _LOCK:
        _CONFIG["bytes_limit"] = None
        _CONFIG["refuse"] = None
        _OWNERS.clear()
        _TRACKED[:] = []
        _PRUNE_AT[0] = 64
        _LEDGERS.clear()
        _LEDGER_ORDER[:] = []
        _HEADROOM_SEEN.clear()
        _PEAK_ESTIMATE.clear()


# ------------------------------------------------------------- ownership --

# explicit providers: owner -> callable yielding the arrays that owner holds
_OWNERS = {}
# weakref-tracked objects: (owner, weakref(obj), extract) — extract(obj)
# returns the arrays; dead refs prune on walk.  Subsystems with short-lived
# instances (pipes, train loops) register here so their death needs no
# unregister call.
_TRACKED = []
_PRUNE_AT = [64]        # the length at which ``track`` drops the dead


def register_owner(name, provider):
    """``provider()`` returns the arrays (anything with ``nbytes``) the
    subsystem currently holds.  The attribution walk matches them against
    ``jax.live_arrays()`` by identity, so providers must yield the VERY
    objects they hold, not copies."""
    with _LOCK:
        _OWNERS[str(name)] = provider
    return provider


def unregister_owner(name):
    with _LOCK:
        _OWNERS.pop(str(name), None)


def track(name, obj, extract):
    """Weakref registration: ``extract(obj)`` yields the arrays ``obj``
    holds; the entry dies with the object (dropped at the next walk, or
    here once the dead could be half the list: a process that registers
    for a week and never asks must not grow)."""
    with _LOCK:
        _TRACKED.append((str(name), weakref.ref(obj), extract))
        if len(_TRACKED) > _PRUNE_AT[0]:
            _TRACKED[:] = [e for e in _TRACKED if e[1]() is not None]
            _PRUNE_AT[0] = 2 * len(_TRACKED) + 64


# the parts of a train state (``parallel.train.TrainState``: a dict of
# ``params``, ``opt`` and, for some models, ``running``) and the owner each
# is attributed to: what a user can act on (the model's size, the
# optimizer's choice and its sharding, the statistics a model carries)
STATE_OWNERS = {"params": "params", "opt": "opt_state", "running": "running"}


def _state_part(state, key):
    """The leaves of a train state under ``key``; under None, what no
    owner of ``STATE_OWNERS`` names (all of a state that is no such dict)."""
    import jax

    parted = isinstance(state, dict) and "params" in state
    if key is None:
        rest = state if not parted else \
            {k: v for k, v in state.items() if k not in STATE_OWNERS}
        return jax.tree.leaves(rest)
    return jax.tree.leaves(state.get(key)) if parted else ()


def track_state(holder, read):
    """Registers, weakly, the train state that ``holder`` carries:
    ``read(holder)`` is its CURRENT state (a step donates the one before, so
    it is read at every walk, never kept), or None.  Its ``params``, ``opt``
    and ``running`` go to the owners ``params``, ``opt_state`` and
    ``running``; whatever else it holds, and the whole of a state that is not
    such a dict, to ``train_state``.  The one way a state's parts are named:
    ``StepTrainer`` and ``TrainLoop`` both register through it."""
    for key, owner in list(STATE_OWNERS.items()) + [(None, "train_state")]:
        track(owner, holder,
              lambda h, key=key: _state_part(read(h), key))


def _itself(a):
    return (a,)


def track_arrays(name, tree):
    """Registers the arrays of ``tree`` themselves, each weakly: an entry
    goes when its array does (batches staged for ``run_steps``, which no
    object of the program holds)."""
    import jax

    with _LOCK:
        have = {id(ref()) for n, ref, _ in _TRACKED if n == name}
    for a in jax.tree.leaves(tree):
        if hasattr(a, "nbytes") and id(a) not in have:
            try:
                track(name, a, _itself)
            except TypeError:
                pass                        # a numpy array takes no weakref


def _iter_owned():
    """(owner, array) pairs from every registration plus the built-in
    providers (scope state, HostPS caches, warm twins).  Every leg is
    best-effort: attribution must never take a run down."""
    with _LOCK:
        owners = list(_OWNERS.items())
        tracked = list(_TRACKED)
    for name, provider in owners:
        try:
            for a in provider() or ():
                yield name, a
        except Exception:
            continue
    dead = False
    for name, ref, extract in tracked:
        obj = ref()
        if obj is None:
            dead = True
            continue
        try:
            for a in extract(obj) or ():
                yield name, a
        except Exception:
            continue
    if dead:
        with _LOCK:
            _TRACKED[:] = [e for e in _TRACKED if e[1]() is not None]
    # built-in: executor scope state (the persistables every step re-writes)
    try:
        from ..scope import global_scope

        for v in list(global_scope()._vars.values()):
            if v is not None and hasattr(v, "nbytes"):
                yield "scope", v
    except Exception:
        pass
    # built-in: HostPS hot-row cache slot buffers (one [slots, dim] array
    # per cached table)
    try:
        from ..hostps import service as _svc

        for emb in _svc.live_embeddings():
            cache = getattr(emb, "cache", None)
            if cache is not None:
                yield "hostps_cache", cache._values
    except Exception:
        pass
    # built-in: warm donation-free twins — a disk-deserialized executable
    # awaiting its re-donate swap pins its first run's state/feed buffers
    # through the fallback closure (executor._WarmLoaded.pinned)
    try:
        from .. import executor as _exec

        with _exec._PROCESS_CACHE_LOCK:
            entries = list(_exec._PROCESS_CACHE.values())
        import jax

        for entry in entries:
            pinned = getattr(entry[0], "pinned", None)
            if pinned is None:
                continue
            for a in jax.tree.leaves(pinned):
                if hasattr(a, "nbytes"):
                    yield "warm_twin", a
    except Exception:
        pass


def _array_devices(a):
    try:
        return [str(d) for d in a.devices()]
    except Exception:
        dev = getattr(a, "device", None)
        return [str(dev)] if dev is not None else ["?"]


def _device_shares(a, nb):
    """``[(device, bytes)]`` of one live array.  Per-device footprint: a
    REPLICATED array costs its full nbytes on every device (each holds a
    copy); only a sharded one splits.  Getting this wrong would
    overestimate headroom on the estimated path by exactly the
    replicated-params factor."""
    devs = _array_devices(a)
    try:
        replicated = a.sharding.is_fully_replicated
    except Exception:
        replicated = False
    share = nb if replicated and len(devs) > 1 else nb / max(len(devs), 1)
    return [(d, share) for d in devs]


def attribution():
    """Classify ``jax.live_arrays()`` by owner: ``{"owners": {owner: bytes,
    ..., "unattributed": bytes}, "device_owners": {device: {owner: bytes}},
    "device_live_bytes": {device: bytes}, "live_bytes": total, "arrays":
    n}``.  A sharded array's bytes split evenly across its devices, a
    replicated one counts whole on each, in ``device_owners`` (with its own
    ``unattributed``) as in ``device_live_bytes``, which feeds the headroom
    estimate so one sample pays exactly one live_arrays() walk."""
    import jax

    owner_of = {}
    for name, a in _iter_owned():
        owner_of.setdefault(id(a), name)
    owners = {}
    per_dev = {}
    total = 0
    n = 0
    for a in jax.live_arrays():
        nb = int(getattr(a, "nbytes", 0) or 0)
        if not nb:
            continue
        n += 1
        total += nb
        owner = owner_of.get(id(a), "unattributed")
        owners[owner] = owners.get(owner, 0) + nb
        for d, share in _device_shares(a, nb):
            by_owner = per_dev.setdefault(d, {})
            by_owner[owner] = by_owner.get(owner, 0) + share
    owners.setdefault("unattributed", 0)
    return {"owners": owners,
            "device_owners": {d: {o: int(b) for o, b in by.items()}
                              for d, by in per_dev.items()},
            "device_live_bytes": {d: int(sum(by.values()))
                                  for d, by in per_dev.items()},
            "live_bytes": total, "arrays": n}


def _live_bytes_per_device():
    """Summed live-array bytes a device, nobody's owner asked."""
    import jax

    per_dev = {}
    for a in jax.live_arrays():
        nb = int(getattr(a, "nbytes", 0) or 0)
        for d, share in _device_shares(a, nb) if nb else ():
            per_dev[d] = per_dev.get(d, 0) + share
    return {d: int(b) for d, b in per_dev.items()}


# -------------------------------------------------------------- headroom --

def headroom(live=None):
    """Per local device: ``{device: {"bytes_limit", "bytes_in_use",
    "headroom", ["estimated"]}}``.  ``bytes_limit`` falls back to the
    configured override; ``bytes_in_use`` falls back (flagged
    ``estimated``) to the summed live-array bytes on that device — the
    framework-visible lower bound, what the CPU drill runs on.  ``live``
    optionally passes a precomputed per-device live-bytes map (a sampler
    that already ran ``attribution()`` hands its ``device_live_bytes``
    over instead of paying a second live_arrays walk)."""
    import jax

    out = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        # configured override FIRST, backend second — the same precedence
        # min_device_bytes_limit gives the capacity router, so admission,
        # occupancy gauges, and routing all budget against one number (an
        # operator capping at 0.8*HBM caps the predictor too, not just
        # the router)
        limit = _configured_limit() or stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use")
        h = {"bytes_limit": int(limit) if limit else None}
        if in_use is None and limit:
            if live is None:
                live = _live_bytes_per_device()
            in_use = live.get(str(d), 0)
            h["estimated"] = True
        h["bytes_in_use"] = int(in_use) if in_use is not None else None
        h["headroom"] = (int(limit) - int(in_use)
                         if limit and in_use is not None else None)
        out[str(d)] = h
    return out


def hbm_frac(live=None):
    """``{device: bytes_in_use / bytes_limit}`` where both are known."""
    out = {}
    for dev, h in headroom(live=live).items():
        if h.get("bytes_limit") and h.get("bytes_in_use") is not None:
            out[dev] = round(h["bytes_in_use"] / h["bytes_limit"], 4)
    return out


def min_device_bytes_limit(fallback=None):
    """The tightest per-device byte limit across ALL local devices — the
    shared capacity number the embedding router and the admission math
    agree on (a single-device read would overbudget a host whose devices
    differ).  Configured override first, then the backend, then
    ``fallback``."""
    cfg = _configured_limit()
    if cfg is not None:
        return cfg
    limits = []
    try:
        import jax

        for d in jax.local_devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:
                continue
            if stats.get("bytes_limit"):
                limits.append(int(stats["bytes_limit"]))
    except Exception:
        pass
    if limits:
        return min(limits)
    return fallback


def loaded_code_bytes():
    """``{device: bytes}``: the generated code of every executable the
    process has loaded, by the device it is loaded on.  The allocator's
    ``bytes_in_use`` holds it beside the arrays (seen on the v5e: a train
    step's 0.05 to 0.17 GB, the float32 programs of a reference check 0.05),
    so an account of ``bytes_in_use`` by owner needs it; a backend that says
    nothing of an executable's size (the CPU) gives 0."""
    import jax

    out = {}
    for client in {d.client for d in jax.local_devices()}:
        for ex in client.live_executables():
            try:
                size = int(ex.size_of_generated_code_in_bytes())
                devices = [str(d) for d in ex.local_devices()]
            except Exception:
                continue
            for d in devices:
                out[d] = out.get(d, 0) + size
    return out


# the allocator's four counters a watermark holds
WATERMARK_FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                    "peak_bytes_reserved")
_PEAK_ESTIMATE = {}     # device -> the largest live-array estimate so far


def watermark(devices=None):
    """``WATERMARK_FIELDS`` and ``device`` of the FULLEST of ``devices``
    (default: the local ones) by its two peaks together, straight from
    ``memory_stats()``: one call a device.  Where the backend keeps no such
    counters (the CPU) the summed live-array bytes stand for
    ``bytes_in_use``, their largest so far for its peak, 0 for the
    reserved pair, and ``estimated`` is True.  None where nothing can be
    said.  Never called from a step: the compile ledger's phases take one as
    they close (``CompileLedger.phase``), and a reader after a run."""
    import jax

    devices = list(devices) if devices is not None else jax.local_devices()
    marks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if "bytes_in_use" in stats:
            marks.append(dict({f: int(stats.get(f, 0))
                               for f in WATERMARK_FIELDS}, device=str(d)))
    if not marks and devices:
        try:
            live = _live_bytes_per_device()
        except Exception:
            return None
        for d in map(str, devices):
            in_use = live.get(d, 0)
            with _LOCK:
                peak = _PEAK_ESTIMATE[d] = max(_PEAK_ESTIMATE.get(d, 0),
                                               in_use)
            marks.append({"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                          "bytes_reserved": 0, "peak_bytes_reserved": 0,
                          "device": d, "estimated": True})
    return max(marks, key=_two_peaks, default=None)


def _two_peaks(mark):
    return mark["peak_bytes_in_use"] + mark["peak_bytes_reserved"]


# -------------------------------------------------- host-side accounting --

def host_accounting():
    """Host-RAM side of the story: process RSS, HostPS table resident bytes
    (initialized rows x row footprint), ShardPS wire replay-log bytes."""
    out = {}
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        out["rss_bytes"] = rss_pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        pass
    try:
        from ..hostps import service as _svc

        total = 0
        for emb in _svc.live_embeddings():
            t = getattr(emb.table, "local_table", emb.table)
            total += int(getattr(t, "nbytes_resident", 0) or 0)
        if total:
            out["hostps_tables_bytes"] = total
    except Exception:
        pass
    try:
        from ..hostps import shard_router as _sr

        total = 0
        for router in list(getattr(_sr, "_LIVE_ROUTERS", ())):
            for st in router._shards.values():
                with st.cond:
                    entries = list(st.log)
                for _seq, rows, values, _lr in entries:
                    total += int(getattr(rows, "nbytes", 0) or 0)
                    total += int(getattr(values, "nbytes", 0) or 0)
        if total:
            out["ps_replay_bytes"] = total
    except Exception:
        pass
    return out


# -------------------------------------------------------- program ledger --

# ident -> ledger dict, insertion-ordered (bench reads the NEW entries per
# config via ledgers()[n:])
_LEDGERS = {}
_LEDGER_ORDER = []
_LEDGER_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                  "generated_code_bytes", "alias_bytes")


def program_ledger(compiled):
    """``Compiled.memory_analysis()`` as a plain dict, or None when the
    backend cannot say: the five ``_LEDGER_FIELDS`` as the backend counts
    them and, where it gives one, ``peak_bytes`` (``peak_memory_in_bytes``;
    ``temp_held_bytes`` says what it is for).  Accepts the executor's warm
    wrapper (unwraps its ``.compiled``)."""
    compiled = getattr(compiled, "compiled", compiled)
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    if isinstance(ma, (list, tuple)):          # per-device list on some jax
        ma = ma[0] if ma else None
        if ma is None:
            return None

    def field(name):
        try:
            v = getattr(ma, name + "_in_bytes", None)
            if v is None:
                v = getattr(ma, name + "_size_in_bytes", None)
            return int(v) if v is not None and int(v) >= 0 else None
        except Exception:
            return None

    led = {"argument_bytes": field("argument_size"),
           "output_bytes": field("output_size"),
           "temp_bytes": field("temp_size"),
           "generated_code_bytes": field("generated_code_size"),
           "alias_bytes": field("alias_size")}
    if all(v is None for v in led.values()):
        return None
    # the buffer assignment's total, where the backend says (0: it does not)
    led["peak_bytes"] = field("peak_memory") or None
    return {k: v for k, v in led.items() if v is not None}


def ledgers():
    """[(ident, ledger)] in record order (process lifetime)."""
    with _LOCK:
        return [(i, dict(_LEDGERS[i])) for i in _LEDGER_ORDER]


def model_bytes(ledger):
    """The ledger's dispatch-time requirement: temp + output bytes (the
    arguments already exist; generated code is negligible next to them)."""
    if not ledger:
        return None
    t = ledger.get("temp_bytes")
    o = ledger.get("output_bytes")
    if t is None and o is None:
        return None
    return temp_held_bytes(ledger) + int(o or 0)


def _io_bytes(ledger):
    """Arguments and outputs, a donated pair once."""
    return (int(ledger.get("argument_bytes") or 0)
            + int(ledger.get("output_bytes") or 0)
            - int(ledger.get("alias_bytes") or 0))


def temp_held_bytes(ledger):
    """The temporaries the program holds beside its arguments and outputs,
    by the buffer assignment.  ``temp_bytes`` is that on the CPU.  The TPU's
    is not: its count holds AGAIN what the program's loops carry of the
    arguments (the stacked weights that a scan over layers hands from trip
    to trip, the state a scan over steps carries), so a donated train step's
    reads near the program's whole footprint (seen on steps compiled for a
    described v5e: 10.09 GB where the assignment's report holds 6.46 of
    temporaries beside 9.60 of state).  There ``peak_bytes`` is the
    assignment's total, the ``Total bytes used`` of the compiler's
    memory-usage report to the byte, and the temporaries are what it holds
    beyond arguments and outputs.  ``peak_bytes`` is believed only where the
    two counts can both be true: it takes off ``temp_bytes`` no more than the
    arguments and a sixty-fourth (the CPU's ``peak_memory_in_bytes`` is another quantity, under
    its arguments' own bytes, and fails this)."""
    raw = int(ledger.get("temp_bytes") or 0)
    if ledger.get("peak_bytes"):
        held = int(ledger["peak_bytes"]) - _io_bytes(ledger)
        least = raw - int(ledger.get("argument_bytes") or 0) - raw // 64
        if least <= held <= raw:        # raw // 64: the loops' own counters
            return held
    return raw


def need_bytes(ledger):
    """What one chip must hold to run the program once: argument + output
    - alias + temp + generated code, ``temp`` the buffer assignment's
    (``temp_held_bytes``).  Donation is why alias comes off: an output that
    takes a donated argument's buffer is counted once.  The one definition:
    the chip's account (``trainer_ledgers``) and the described chip's count
    (``scripts/step_memory_count.py``) both print it through
    ``need_line``."""
    if not ledger:
        return None
    return (_io_bytes(ledger) + temp_held_bytes(ledger)
            + int(ledger.get("generated_code_bytes") or 0))


def need_line(label, ledger):
    """The one line that says what a program needs, in GB (1e9 bytes)."""
    gb = {k: (ledger.get(k) or 0) / 1e9 for k in _LEDGER_FIELDS}
    held = temp_held_bytes(ledger)
    return ("need: %s argument %.6f + output %.6f - alias %.6f + temp %.6f "
            "+ generated code %.6f = %.6f GB%s"
            % (label, gb["argument_bytes"], gb["output_bytes"],
               gb["alias_bytes"], held / 1e9, gb["generated_code_bytes"],
               need_bytes(ledger) / 1e9,
               "" if held == (ledger.get("temp_bytes") or 0) else
               " (temp by the buffer assignment's total %.6f; "
               "memory_analysis() counts %.6f, with what the loops carry "
               "of the arguments)" % (ledger["peak_bytes"] / 1e9,
                                      gb["temp_bytes"])))


def _publish(registry, ident, led):
    """The ledger's gauges, one set a program."""
    for k in _LEDGER_FIELDS:
        if led.get(k) is not None:
            registry.gauge("monitor.mem.program.%s" % k,
                           program=ident).set(led[k])
    registry.gauge("monitor.mem.program.need_bytes",
                   program=ident).set(need_bytes(led))


def _remember(ident, led):
    """Keeps the ledger process-wide (the headroom predictor, the OOM
    postmortem, ``ledgers()``)."""
    with _LOCK:
        prev = _LEDGERS.get(ident)
        if prev is None:
            _LEDGER_ORDER.append(ident)
        _LEDGERS[ident] = led
        if prev is not None and prev != led:
            # a recompiled variant of the same ident (feed-shape drift)
            # carries a NEW requirement: un-mark it so the headroom
            # predictor re-runs against the bigger ledger instead of
            # resting on the old verdict
            _HEADROOM_SEEN.discard(ident)


def trainer_ledgers():
    """``{label: ledger}`` of the programs the trainers dispatched
    (``<label>.step``, ``<label>.run_steps``: ``monitor/devscope.py``'s
    registry), a device's share each, by the same ``program_ledger`` as the
    executor path, kept with that path's ledgers and mirrored into its
    ``monitor.mem.program.*{program=}`` gauges (the active session's
    registry, else the default one).  The executables are devscope's, fetched
    once whoever asks first; a program the backend says nothing of is left
    out.  For after a run or at a set-up phase, never for a step."""
    from . import devscope, session
    from .registry import default_registry

    mon = session.active()
    registry = mon.registry if mon is not None else default_registry()
    out = {}
    for label, compiled in devscope.executables().items():
        led = program_ledger(compiled)
        if led is None:
            continue
        out[label] = led
        _remember(label, led)
        try:
            _publish(registry, label, led)
        except Exception:
            pass
    return out


def largest_values(label, n=10):
    """The ``n`` largest values of the trainer program ``label`` by its
    compiled text (``devscope.value_sizes``: the entry computation's results
    and what its loops carry), each ``{"bytes", "shape", "instruction",
    "phase", "scope", "op_name"}`` with ``devscope.classify``'s reading of
    its ``op_name``.  No liveness, so no peak: the candidates for one."""
    from . import devscope

    compiled = devscope.executables().get(label)
    if compiled is None:
        return []
    out = []
    for size, shape, name, op_name in \
            devscope.value_sizes(compiled.as_text())[:n]:
        phase, scope = devscope.classify(op_name) if op_name else (None, None)
        out.append({"bytes": size, "shape": shape, "instruction": name,
                    "phase": phase, "scope": scope, "op_name": op_name})
    return out


def record_program(mon, ident, compiled, source="compile"):
    """The compiled-program memory ledger hook (executor: cold compile /
    process-cache adoption / warm disk hit).  Gauges
    ``monitor.mem.program.*{program=ident}`` + one ``mem_program`` timeline
    event carrying ``source``.  Returns the ledger (also kept process-wide
    for the headroom predictor and the OOM postmortem)."""
    led = program_ledger(compiled)
    if led is None:
        try:
            mon.timeline.emit("mem_program", ident=ident, source=source,
                              available=False)
        except Exception:
            pass
        return None
    _remember(ident, led)
    try:
        _publish(mon.registry, ident, led)
        mon.timeline.emit("mem_program", ident=ident, source=source,
                          available=True, **led)
    except Exception:
        pass
    return led


# ---------------------------------------------------- headroom predictor --

_HEADROOM_SEEN = set()     # idents already checked (one verdict per ident)


def predict_dispatch(mon, ident, ledger=None):
    """Pre-dispatch admission math for a newly compiled/adopted program:
    compare its temp+output requirement against every local device's
    ``bytes_limit - bytes_in_use``.  One ``mem_headroom`` verdict event per
    ident; a predicted shortfall warns (+ ``monitor.mem.predicted_oom``)
    and, in refuse mode, raises ``MemoryBudgetError`` instead of letting
    the dispatch die."""
    with _LOCK:
        if ident in _HEADROOM_SEEN:
            return
        _HEADROOM_SEEN.add(ident)
        ledger = ledger or _LEDGERS.get(ident)
    need = model_bytes(ledger)
    if need is None:
        return
    try:
        hr = headroom()
    except Exception:
        return
    short = None
    for dev, h in hr.items():
        if h.get("headroom") is None:
            continue
        if need > h["headroom"]:
            short = (dev, h)
            break
    ev = {"ident": ident, "need_bytes": need,
          "predicted_oom": short is not None}
    if short is not None:
        dev, h = short
        ev.update(device=dev, bytes_limit=h.get("bytes_limit"),
                  bytes_in_use=h.get("bytes_in_use"),
                  headroom=h.get("headroom"),
                  estimated=bool(h.get("estimated")))
    try:
        mon.timeline.emit("mem_headroom", **ev)
        if short is not None:
            mon.registry.counter("monitor.mem.predicted_oom").incr()
            mon.timeline.flush()   # the warning must survive the death it
            # predicts — the whole point of predicting
    except Exception:
        pass
    if short is not None:
        dev, h = short
        msg = ("memscope: program %s needs ~%d bytes of temp+output but "
               "device %s has only %s bytes of headroom (%s in use of %s "
               "limit%s) — a dispatch is likely to RESOURCE_EXHAUST"
               % (ident, need, dev, h.get("headroom"), h.get("bytes_in_use"),
                  h.get("bytes_limit"),
                  ", framework-estimated" if h.get("estimated") else ""))
        if refuse_enabled():
            # the admission refusal must stay ARMED: un-mark the ident so a
            # retry of the same program re-runs the math (and re-refuses
            # until headroom actually improves) instead of sailing through
            # the warn-once dedup into the OOM the refusal exists to stop
            with _LOCK:
                _HEADROOM_SEEN.discard(ident)
            raise MemoryBudgetError(msg)
        warnings.warn(msg, stacklevel=2)


# -------------------------------------------------------- OOM postmortem --

def is_resource_exhausted(exc):
    """True for a real XLA RESOURCE_EXHAUSTED, an injected ``oom_step``
    fault, or the refuse-mode admission error."""
    if isinstance(exc, (InjectedOOMError, MemoryBudgetError)):
        return True
    s = str(exc)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def oom_extra(mon, ident=None):
    """The flight-recorder ``extra`` for an OOM: failing program's ledger,
    the headroom math, the top-K live owners, and the watermark tail."""
    with _LOCK:
        led = dict(_LEDGERS[ident]) if ident in _LEDGERS else None
    sec = {"failing_program": ident, "ledger": led,
           "need_bytes": model_bytes(led)}
    try:
        sec["headroom"] = headroom()
    except Exception:
        pass
    try:
        attr = attribution()
        owners = attr.get("owners", {})
        top = sorted(((o, b) for o, b in owners.items()
                      if o != "unattributed"), key=lambda kv: -kv[1])[:8]
        sec["owners_top"] = [{"owner": o, "bytes": int(b)} for o, b in top]
        sec["unattributed_bytes"] = int(owners.get("unattributed", 0))
        sec["live_bytes"] = attr.get("live_bytes")
    except Exception:
        pass
    try:
        sec["host"] = host_accounting()
    except Exception:
        pass
    try:
        sec["watermark_tail"] = [e for e in mon.timeline.tail()
                                 if e.get("ev") == "memory"][-4:]
    except Exception:
        pass
    return {"mem_oom": sec}


def note_oom(mon, ident, exc):
    """RESOURCE_EXHAUSTED landed: count it and dump the flight postmortem
    with the memory section.  Dedup rides the flight recorder's
    one-dump-per-exception-object contract, so the trainer's own later
    dump of the same exception is a no-op."""
    try:
        mon.registry.counter("monitor.mem.oom").incr()
    except Exception:
        pass
    flight = getattr(mon, "flight", None)
    if flight is None:
        return None
    try:
        return flight.dump(exc=(type(exc), exc, exc.__traceback__),
                           reason="resource_exhausted",
                           extra=oom_extra(mon, ident))
    except Exception:
        return None
