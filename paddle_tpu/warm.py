"""WarmStart: a persistent compiled-executable store + topology pre-compile.

The problem (ROADMAP item 5): the executor already keys compiled programs
for in-process reuse (executor.py compile cache), but the key dies with the
process — every elastic restart, preemption respawn, shrink/grow relaunch
and serving-replica spin-up re-pays multi-second XLA compiles, and a
restart storm multiplies that by the world size.  The reference framework
ships the cure as a first-class feature: the inference stack serializes its
analysis-optimized program (and TensorRT engine caches) to disk so a warm
process never re-optimizes.  This module is that idea for every compiled
artifact in the repo:

- ``ExecutableStore``: a disk directory of serialized XLA executables
  (``jax.experimental.serialize_executable``), keyed by the SAME components
  the executor's in-memory cache uses — program content fingerprint, input
  aval signature, fetch/state sets, mesh/topology descriptor, donation +
  sentinel flags — plus the jax/jaxlib/platform version fingerprint.
  Entries are CRC-covered and published atomically (tmp + ``os.replace``,
  the shard/COMMIT idiom of parallel/checkpoint.py), with keep-last-N
  retention.  A corrupt, version-skewed or otherwise poisoned entry is
  REFUSED (counted, removed) and the caller silently recompiles and
  overwrites — the cache can slow a restart down to cold, never wedge it
  or mis-execute a step;
- ``WarmCallable``: jit-with-a-memory for raw step functions
  (parallel/train.py ``make_train_step``, the ExportedPredictor call): AOT
  lower+compile on first use, persisted through the store, deserialized on
  the next process's first use;
- a pre-compile registry: after a COMMITTED checkpoint
  (ft/ckpt.TrainStateWriter -> ``notify_commit``) a background daemon
  thread runs registered pre-compilers — e.g. ``topology_precompiler``
  compiling the post-shrink / post-grow world sizes' executables from
  parallel/rules.py specs — so an elastic resize restarts into a warm
  cache instead of compiling what it could have known it would need.

Enablement: the store activates when ``PADDLE_TPU_WARM_DIR`` names a
directory (the launcher's ``--warm_dir`` sets it fleet-wide) or
``configure(dirname)`` is called; ``PADDLE_TPU_WARM=0`` is the kill
switch.  With no store, every surface behaves exactly as before (in-memory
caching only).

Telemetry contract (the PR-2 recompile detector must NOT count a warm hit
as churn): a disk hit emits a ``compile`` timeline event with
``cached="disk"`` + ``deserialize_ms`` and bumps
``monitor.compile.warm_hits``; a consulted-but-empty store bumps
``monitor.compile.warm_misses``; refused entries (CRC / version / flag
drift) bump ``monitor.compile.refused`` on top of the miss.  Module-level
``stats()`` mirrors the counters monitor-free for the bench telemetry
block (``compile_ms`` / ``warm_compile_ms``).

DONATION CONTRACT: persisted executables are always compiled WITHOUT
buffer donation.  Executing a deserialized executable whose HLO aliases
donated inputs corrupts the CPU PJRT client's heap under concurrent
client traffic (jaxlib 0.4.36 — reproduced: deserialize_and_load +
donate_argnums + a device_put on another thread → glibc abort; the
donation-free twin is stable under the same load), and even where it
works, donation pins the restored executable to the saver's aliasing
assumptions.  So: a cold miss runs its donated in-process executable as
always and publishes a donation-free TWIN (compiled on a background
thread — ``PADDLE_TPU_WARM_SYNC_PUBLISH=1`` forces inline for drills);
a warm hit runs the safe twin immediately and, when the caller wanted
donation, re-compiles the donated variant in the background and swaps it
in — warm now, buffer-optimal a few seconds later, bit-identical either
way (donation never changes numerics).
"""

import hashlib
import json
import os
import pickle
import threading
import time
import warnings
import zlib

import numpy as np
import jax

__all__ = [
    "configure", "store", "reset", "enabled", "stats", "reset_stats",
    "ExecutableStore", "WarmCallable", "version_fingerprint",
    "program_fingerprint", "mesh_desc", "aval_signature", "key_digest",
    "tree_avals", "strip_donation", "publish_executable",
    "code_fingerprint",
    "spawn_background", "join_background", "sync_publish",
    "note_compile_ms", "note_poisoned",
    "register_precompiler", "clear_precompilers", "notify_commit",
    "precompile_thread", "topology_worlds", "topology_precompiler",
    "measure_roundtrip_ms",
]

_MAGIC = b"ptwarm1\n"
_SUFFIX = ".warm"


def enabled():
    """Global kill switch (``PADDLE_TPU_WARM=0``)."""
    return os.environ.get("PADDLE_TPU_WARM", "1").strip() != "0"


def _default_keep():
    try:
        return int(os.environ.get("PADDLE_TPU_WARM_KEEP", "64"))
    except ValueError:
        return 64


# ---------------------------------------------------------------- stats --

_STATS_LOCK = threading.Lock()


def _zero_stats():
    return {"warm_hits": 0, "warm_misses": 0, "refused": 0, "poisoned": 0,
            "published": 0, "precompiled": 0, "precompile_errors": 0,
            "compile_ms": 0.0, "deserialize_ms": 0.0, "serialize_ms": 0.0}


_STATS = _zero_stats()

# counters mirrored into the monitor registry when a session is active
_REG_COUNTERS = {
    "warm_hits": "monitor.compile.warm_hits",
    "warm_misses": "monitor.compile.warm_misses",
    "refused": "monitor.compile.refused",
    "poisoned": "monitor.compile.poisoned",
    "precompiled": "monitor.compile.precompiled",
}
_REG_HISTOGRAMS = {
    "deserialize_ms": "monitor.compile.deserialize_ms",
    "compile_ms": "monitor.compile.cold_ms",
}


def _note(name, value=1):
    with _STATS_LOCK:
        _STATS[name] += value
    try:
        from . import monitor as _monitor

        mon = _monitor.active()
        if mon is None:
            return
        if name in _REG_COUNTERS:
            mon.registry.counter(_REG_COUNTERS[name]).incr(int(value))
        elif name in _REG_HISTOGRAMS:
            mon.registry.histogram(_REG_HISTOGRAMS[name]).observe(value)
    except Exception:
        pass                     # telemetry must never fail a compile


def note_compile_ms(ms):
    """Executor hook: one cold XLA compile's wall ms (feeds the bench
    telemetry block's ``compile_ms`` even when no store is active)."""
    _note("compile_ms", ms)


def note_poisoned():
    """Executor hook: a disk-loaded executable failed its first call."""
    _note("poisoned")


def stats():
    """Process-lifetime WarmStart counters (monitor-free: the bench
    telemetry block reads deltas of these)."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_stats():
    global _STATS
    with _STATS_LOCK:
        _STATS = _zero_stats()


# ----------------------------------------------------------- fingerprints --

def version_fingerprint():
    """The environment half of every cache key: an executable compiled by a
    different jax/jaxlib, another backend platform or another device kind
    must never load (XLA serialization is not stable across them)."""
    import jaxlib

    try:
        devs = jax.devices()
        device = devs[0].device_kind if devs else "none"
        ndev = len(devs)
    except Exception:
        device, ndev = "none", 0
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(), "device": device,
            "ndev": ndev}


def _canonical(obj):
    """JSON-stable view of a key component: tuples/lists/dicts recurse,
    numpy scalars become numbers, sets sort, everything else falls back to
    ``repr`` (stable for the PartitionSpec / dtype / flag objects keys
    carry)."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, (list, tuple)):
        return [_canonical(x) for x in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(_canonical(x) for x in obj)
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(
            obj.items(), key=lambda kv: str(kv[0]))}
    return repr(obj)


def key_digest(key_parts):
    """Hex digest of the canonical JSON of ``key_parts`` — the entry's file
    name.  The version fingerprint is NOT folded in: it rides the entry
    header and is verified on load, so a version-skewed entry is REFUSED
    (counted) rather than silently shadowed by a fresh file name."""
    blob = json.dumps(_canonical(key_parts), sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:40]


def program_fingerprint(program):
    """Content hash of a framework Program: ops (type, slots, attrs), var
    shapes/dtypes/persistability, and the random seed.  Unlike the
    in-memory cache's per-object identity this survives the process — the
    respawned worker rebuilds the same program and lands on the same
    entry."""
    blocks = []
    for block in program.blocks:
        ops = [[op.type,
                _canonical(sorted(op.inputs.items())),
                _canonical(sorted(op.outputs.items())),
                _canonical(op.attrs)] for op in block.ops]
        vars_ = [[name,
                  _canonical(getattr(v, "shape", None)),
                  repr(getattr(v, "dtype", None)),
                  bool(getattr(v, "persistable", False))]
                 for name, v in sorted(block.vars.items())]
        blocks.append([block.idx, ops, vars_])
    blob = json.dumps(_canonical([blocks, program.random_seed]),
                      sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:40]


def code_fingerprint(*fns):
    """Best-effort content hash of python callables (bytecode + consts +
    names + qualname, recursing one level into code-object consts).  Keys
    that name a model (``WarmCallable``'s ``key_parts``) fold this in so
    editing the loss or optimizer math invalidates the persisted executable even when every
    shape and spec stays the same.  Closure VALUES are not hashable here —
    a fn closing over changed data still needs a new key from the caller."""
    h = hashlib.sha256()
    for fn in fns:
        code = getattr(fn, "__code__", None)
        h.update(getattr(fn, "__qualname__", repr(fn)).encode())
        if code is None:
            continue
        h.update(code.co_code)
        h.update(repr(code.co_names).encode())
        for const in code.co_consts:
            inner = getattr(const, "co_code", None)
            h.update(inner if inner is not None else repr(const).encode())
    return h.hexdigest()[:24]


def mesh_desc(mesh):
    """Durable descriptor of a mesh topology (device object ids die with
    the process; axis names + sizes + device kind + process span do not)."""
    if mesh is None:
        return None
    try:
        axes = [(str(a), int(s)) for a, s in
                zip(mesh.axis_names, mesh.devices.shape)]
        kinds = sorted({d.device_kind for d in mesh.devices.flat})
        procs = sorted({d.process_index for d in mesh.devices.flat})
    except Exception:
        return repr(mesh)
    return {"axes": axes, "kinds": kinds, "nproc": len(procs)}


def _aval_of(x):
    """ShapeDtypeStruct view of one argument (sharding kept when the live
    array carries one); non-array leaves (python scalars) pass through —
    they lower concretely and identically either way."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    sharding = getattr(x, "sharding", None)
    try:
        if sharding is not None:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    except Exception:
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


def tree_avals(args):
    """Aval pytree of a call's arguments — what a background (re)compile
    lowers from, so it never pins (or races) the live buffers."""
    return jax.tree_util.tree_map(_aval_of, args)


def aval_signature(args):
    """Shape/dtype signature of a call's arguments — ShapeDtypeStructs,
    jax/numpy arrays and python scalars all normalize the same way, so a
    pre-compile over avals and the live call over arrays share one key."""
    def leaf(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return "%s%s" % (np.dtype(dtype).name, tuple(shape))
        return "py:%s" % type(x).__name__

    return _canonical(jax.tree_util.tree_map(leaf, args))


# ----------------------------------------------------------------- store --

class _Refused(Exception):
    """An entry that must not load.  ``remove`` says whether the file
    itself is junk (corrupt/truncated: delete it) or merely wrong for THIS
    process (version skew: leave it for the peers it may still fit)."""

    def __init__(self, msg, remove=True):
        super().__init__(msg)
        self.remove = remove


def _exec_devices(compiled):
    """``{"platform", "ids"}`` of the devices ``compiled`` runs on — the
    mesh's devices for a sharded step, the one device otherwise."""
    devs = compiled.runtime_executable().local_devices()
    return {"platform": devs[0].platform, "ids": [d.id for d in devs]}


def _load_on(devices, serialized):
    """Deserialize onto exactly the devices the executable was compiled
    for.  ``deserialize_and_load`` defaults to EVERY device of the backend,
    which turns a one-device executable into one that expects a shard per
    device on any host with more than one."""
    from jax.experimental import serialize_executable as _se

    if not devices:
        raise _Refused("entry names no devices")
    by_id = {d.id: d for d in jax.devices(devices["platform"])}
    missing = [i for i in devices["ids"] if i not in by_id]
    if missing:
        raise _Refused("compiled for %s devices %s; this process has no %s"
                       % (devices["platform"], devices["ids"], missing),
                       remove=False)
    devs = [by_id[i] for i in devices["ids"]]
    return _se.deserialize_and_load(*serialized, backend=devs[0].client,
                                    execution_devices=devs)


class ExecutableStore:
    """Disk directory of serialized executables.

    Entry file layout (``exec-<digest>.warm``)::

        ptwarm1\\n <8-byte big-endian header length> <header JSON> <payload>

    header: ``{"crc": crc32(payload), "versions": {...}, "devices":
    {"platform", "ids"}, "key": {...}}``;
    payload: ``pickle((serialized, in_tree, out_tree))`` from
    ``jax.experimental.serialize_executable.serialize``.

    Publish is atomic (tmp + ``os.replace``); ``lookup`` verifies the
    version fingerprint and the payload CRC before deserializing and treats
    ANY failure as a refusal: the entry is deleted, the miss is counted,
    and the caller recompiles (and overwrites).  Retention keeps the
    newest ``keep`` entries by access time."""

    def __init__(self, dirname, keep=None):
        self.dirname = str(dirname)
        os.makedirs(self.dirname, exist_ok=True)
        self.keep = _default_keep() if keep is None else int(keep)

    def _path(self, digest):
        return os.path.join(self.dirname, "exec-%s%s" % (digest, _SUFFIX))

    def entries(self):
        try:
            return sorted(n for n in os.listdir(self.dirname)
                          if n.startswith("exec-") and n.endswith(_SUFFIX))
        except OSError:
            return []

    # -- load ------------------------------------------------------------
    def _parse(self, blob):
        if not blob.startswith(_MAGIC):
            raise _Refused("bad magic")
        off = len(_MAGIC)
        if len(blob) < off + 8:
            raise _Refused("truncated header length")
        hlen = int.from_bytes(blob[off:off + 8], "big")
        hdr_end = off + 8 + hlen
        if len(blob) < hdr_end:
            raise _Refused("truncated header")
        try:
            header = json.loads(blob[off + 8:hdr_end].decode("utf-8"))
        except ValueError as e:
            raise _Refused("unparseable header: %s" % e)
        return header, blob[hdr_end:]

    def lookup(self, key_parts, count_miss=True):
        """``(compiled, deserialize_ms)`` or None.  Never raises: a corrupt
        or skewed entry is refused (counted + removed) and reads as a miss
        — the caller's cold path is the fallback."""
        path = self._path(key_digest(key_parts))
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            if count_miss:
                _note("warm_misses")
            return None
        try:
            header, payload = self._parse(blob)
            versions = header.get("versions")
            if versions != version_fingerprint():
                # SKEW, not corruption: the entry may be exactly right for
                # the fleet members still on the other version (shared-fs
                # store mid-rolling-upgrade) — refuse locally, never
                # delete; this process's recompile overwrites it anyway
                raise _Refused(
                    "version skew (entry %s, this process %s)"
                    % (versions, version_fingerprint()), remove=False)
            if (zlib.crc32(payload) & 0xFFFFFFFF) != int(header.get("crc",
                                                                    -1)):
                raise _Refused("payload CRC mismatch")
            compiled = _load_on(header.get("devices"),
                                pickle.loads(payload))
        except Exception as e:
            # poisoned entry: silently fall back to a recompile (which
            # overwrites); the cache must never be able to wedge a step
            _note("refused")
            if count_miss:
                _note("warm_misses")
            if getattr(e, "remove", True):
                try:
                    os.remove(path)
                except OSError:
                    pass
            warnings.warn("warm cache entry %s refused (%s): recompiling"
                          % (os.path.basename(path), e))
            return None
        ms = (time.perf_counter() - t0) * 1e3
        _note("warm_hits")
        _note("deserialize_ms", ms)
        try:
            os.utime(path, None)          # LRU touch for retention
        except OSError:
            pass
        return compiled, ms

    # -- publish ---------------------------------------------------------
    def publish(self, key_parts, compiled):
        """Serialize + atomically publish an executable.  Best-effort: an
        unserializable executable (callbacks, exotic backends) returns None
        and the run simply stays cold — never an error."""
        try:
            from jax.experimental import serialize_executable as _se

            t0 = time.perf_counter()
            payload = pickle.dumps(_se.serialize(compiled))
            devices = _exec_devices(compiled)
            ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            warnings.warn("warm cache: executable not serializable (%s); "
                          "this program stays cold across restarts" % e)
            return None
        header = json.dumps({
            "crc": zlib.crc32(payload) & 0xFFFFFFFF,
            "versions": version_fingerprint(),
            "devices": devices,
            "key": _canonical(key_parts),
            "created": time.time(),
        }).encode("utf-8")
        path = self._path(key_digest(key_parts))
        tmp = "%s.tmp-%d-%d" % (path, os.getpid(), threading.get_ident())
        try:
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(len(header).to_bytes(8, "big"))
                f.write(header)
                f.write(payload)
            os.replace(tmp, path)
        except OSError as e:
            try:
                os.remove(tmp)
            except OSError:
                pass
            warnings.warn("warm cache publish failed: %s" % e)
            return None
        _note("serialize_ms", ms)
        _note("published")
        self._retention()
        return path

    def _retention(self):
        """Keep the newest ``keep`` entries by mtime (lookup touches)."""
        if not self.keep or self.keep <= 0:
            return
        aged = []
        for name in self.entries():
            full = os.path.join(self.dirname, name)
            try:
                aged.append((os.path.getmtime(full), full))
            except OSError:
                continue
        aged.sort()
        for _, full in aged[:-self.keep]:
            try:
                os.remove(full)
            except OSError:
                pass


# ------------------------------------------------------ background work --

_BACKGROUND = set()
_BACKGROUND_LOCK = threading.Lock()
_SHUTTING_DOWN = False


def sync_publish():
    """``PADDLE_TPU_WARM_SYNC_PUBLISH=1``: run publish work inline instead
    of on a background thread — drills and tests that must observe a
    durable store entry before a SIGKILL set this."""
    return os.environ.get("PADDLE_TPU_WARM_SYNC_PUBLISH",
                          "0").strip() == "1"


def spawn_background(name, fn, sync=None):
    """Run ``fn`` on a tracked daemon thread (inline when ``sync`` — or the
    PADDLE_TPU_WARM_SYNC_PUBLISH env for sync=None — says so).  Errors are
    warned and counted, never raised: every background job here is a
    perf optimization, not a correctness step."""

    def _guarded():
        if _SHUTTING_DOWN:
            return              # perf-only work must not delay a process
                                # that is already exiting
        try:
            fn()
        except Exception as e:       # noqa: BLE001 — background QoS
            _note("precompile_errors")
            warnings.warn("warm background job %r failed: %r" % (name, e))

    run_inline = sync_publish() if sync is None else sync
    if run_inline:
        _guarded()
        return None

    def _run():
        try:
            _guarded()
        finally:
            with _BACKGROUND_LOCK:
                _BACKGROUND.discard(t)

    _arm_atexit()
    t = threading.Thread(target=_run, daemon=True, name=name)
    with _BACKGROUND_LOCK:
        _BACKGROUND.add(t)
    t.start()
    return t


def _join_at_exit():
    """Interpreter-exit hook: a daemon thread torn down MID-XLA-COMPILE
    aborts the process (native code under a dying runtime), turning a
    cleanly finished run into rc=134 — so outstanding publishes and
    re-donate compiles get a bounded grace to finish.  The shutdown flag
    keeps queued-but-unstarted jobs from beginning new compile work the
    exiting process would only discard; a job already inside XLA cannot be
    cancelled and is what the grace exists for."""
    global _SHUTTING_DOWN
    _SHUTTING_DOWN = True
    try:
        join_background(timeout=float(
            os.environ.get("PADDLE_TPU_WARM_EXIT_GRACE_SECS", "60")))
    except Exception:
        pass


_ATEXIT_ARMED = False


def _arm_atexit():
    global _ATEXIT_ARMED
    if not _ATEXIT_ARMED:
        import atexit

        atexit.register(_join_at_exit)
        _ATEXIT_ARMED = True


def join_background(timeout=10.0):
    """Wait for outstanding background publishes/recompiles (tests, and
    anything that wants the store durable NOW)."""
    deadline = time.time() + timeout
    while True:
        with _BACKGROUND_LOCK:
            live = [t for t in _BACKGROUND if t.is_alive()]
            _BACKGROUND.difference_update(
                t for t in list(_BACKGROUND) if not t.is_alive())
        t = precompile_thread()
        if t is not None:
            live.append(t)
        if not live or time.time() > deadline:
            return not live
        live[0].join(max(deadline - time.time(), 0.01))


def strip_donation(jit_kwargs):
    """The persisted-executable variant of a jit config: donation removed
    (see the module docstring's donation contract)."""
    return {k: v for k, v in (jit_kwargs or {}).items()
            if k not in ("donate_argnums", "donate_argnames")}


def publish_executable(store_, key_parts, fn, jit_kwargs, args,
                       compiled=None):
    """Persist the donation-free executable for ``fn(*args)``.

    When the in-process ``compiled`` already is donation-free it is
    serialized directly (no second compile); otherwise a twin is compiled
    from the call's AVALS on a background thread (inline under
    PADDLE_TPU_WARM_SYNC_PUBLISH=1) so the training thread never pays it."""
    if store_ is None:
        return None
    jk = dict(jit_kwargs or {})
    if not jk.get("donate_argnums") and not jk.get("donate_argnames"):
        return store_.publish(key_parts, compiled) if compiled is not None \
            else spawn_background(
                "warm-publish",
                lambda: store_.publish(
                    key_parts,
                    jax.jit(fn, **strip_donation(jk)).lower(
                        *tree_avals(args)).compile()))
    avals = tree_avals(args)
    kw = strip_donation(jk)

    def _twin():
        store_.publish(key_parts,
                       jax.jit(fn, **kw).lower(*avals).compile())

    return spawn_background("warm-publish-twin", _twin)


# -------------------------------------------------------- active store --

_STORE = None
_STORE_LOCK = threading.Lock()
_ENV_CHECKED = False


def configure(dirname, keep=None):
    """Activate (or swap) the process's executable store.  ``None``
    deactivates."""
    global _STORE, _ENV_CHECKED
    with _STORE_LOCK:
        _ENV_CHECKED = True
        _STORE = None if dirname is None else ExecutableStore(dirname,
                                                              keep=keep)
        return _STORE


def store():
    """The active ExecutableStore or None.  First call honors
    ``PADDLE_TPU_WARM_DIR`` so launched workers enable the store from the
    environment (the launcher's ``--warm_dir``)."""
    global _ENV_CHECKED, _STORE
    if not enabled():
        return None
    if _STORE is None and not _ENV_CHECKED:
        with _STORE_LOCK:
            if not _ENV_CHECKED:
                _ENV_CHECKED = True
                d = os.environ.get("PADDLE_TPU_WARM_DIR", "").strip()
                if d:
                    _STORE = ExecutableStore(d)
    return _STORE


def reset():
    """Tests: drop the active store, stats and registered pre-compilers."""
    global _STORE, _ENV_CHECKED
    with _STORE_LOCK:
        _STORE = None
        _ENV_CHECKED = False
    reset_stats()
    clear_precompilers()


# ----------------------------------------------------------- WarmCallable --

class WarmCallable:
    """A jit whose compilations persist: AOT ``lower().compile()`` on the
    first call per input signature, loaded from the executable store when a
    previous process already paid the compile.

    ``key_parts`` carries everything that decides the lowering besides the
    argument avals (model/rules fingerprint, mesh descriptor, flags);
    donation rides the key automatically from ``jit_kwargs``.  With no
    active store this degrades to plain in-process AOT caching.

    A disk-loaded executable is verified BY ITS FIRST CALL: any failure
    (aval drift a digest collision slipped past, backend rejection) falls
    back to a fresh compile that overwrites the poisoned entry — warm can
    regress to cold, never to wrong."""

    def __init__(self, fn, key_parts, jit_kwargs=None, label=None,
                 store_=None):
        self.fn = fn
        self.key_parts = key_parts
        self.jit_kwargs = dict(jit_kwargs or {})
        self.label = label or getattr(fn, "__name__", "warm_fn")
        self._store = store_
        self._lock = threading.RLock()   # __call__ re-enters via ensure()
        self._compiled = {}          # sig digest -> compiled
        self._verified = set()       # sig digests proven by a real call
        self.last_source = None      # "cached" | "disk" | "compiled"
        self.compile_ms = None
        self.deserialize_ms = None

    def _active_store(self):
        return self._store if self._store is not None else store()

    def _key(self, args):
        # the label is DISPLAY identity only — the caller's key_parts (plus
        # jit config and avals) decide which entry this is
        return {"kind": "warm_callable",
                "key": _canonical(self.key_parts),
                "jit": _canonical(sorted(self.jit_kwargs.items())),
                "args": aval_signature(args)}

    def _emit(self, cached, ms):
        try:
            from . import monitor as _monitor

            mon = _monitor.active()
            if mon is None:
                return
            ev = {"ident": self.label, "recompile": False, "diff": [],
                  "cached": cached}
            if cached == "disk":
                ev["deserialize_ms"] = round(ms, 3)
            else:
                ev["compile_ms"] = round(ms, 3)
            mon.timeline.emit("compile", **ev)
        except Exception:
            pass

    def _cold(self, key, args, sig):
        t0 = time.perf_counter()
        compiled = jax.jit(self.fn, **self.jit_kwargs).lower(
            *args).compile()
        ms = (time.perf_counter() - t0) * 1e3
        _note("compile_ms", ms)
        st = self._active_store()
        if st is not None:
            # persisted variant is donation-free (module docstring); when
            # this compile already is, it serializes directly, else a twin
            # compiles off-thread
            publish_executable(st, key, self.fn, self.jit_kwargs, args,
                               compiled=compiled)
        self._compiled[sig] = compiled
        self._verified.add(sig)      # freshly compiled for these avals
        self.last_source = "compiled"
        self.compile_ms = ms
        self._emit(False, ms)
        return compiled

    def _redonate(self, args, sig):
        """After a disk hit for a donating callable: the loaded executable
        is the donation-free twin — compile the donated variant in the
        background and swap it in (bit-identical; donation only changes
        buffer reuse)."""
        avals = tree_avals(args)

        def _bg():
            compiled = jax.jit(self.fn, **self.jit_kwargs).lower(
                *avals).compile()
            with self._lock:
                self._compiled[sig] = compiled
                self._verified.add(sig)

        spawn_background("warm-redonate:%s" % self.label, _bg, sync=False)

    def ensure(self, *args):
        """Compile-or-load for this argument signature WITHOUT calling —
        ``args`` may be ``jax.ShapeDtypeStruct`` avals (the pre-compile
        path).  Returns "cached" | "disk" | "compiled"."""
        key = self._key(args)
        sig = key_digest(key)
        with self._lock:
            if sig in self._compiled:
                self.last_source = "cached"
                return "cached"
            st = self._active_store()
            if st is not None:
                hit = st.lookup(key)
                if hit is not None:
                    compiled, ms = hit
                    self._compiled[sig] = compiled
                    self.last_source = "disk"
                    self.deserialize_ms = ms
                    self._emit("disk", ms)
                    if self.jit_kwargs.get("donate_argnums") \
                            or self.jit_kwargs.get("donate_argnames"):
                        self._redonate(args, sig)
                    return "disk"
            self._cold(key, args, sig)
            return "compiled"

    def resolve(self, *args):
        """The raw compiled executable for this argument signature
        (ensuring first) — for hot-path callers that cache it themselves
        and must not pay the key digest per call.  Call through
        ``__call__`` once first if the executable may have come from disk:
        resolve() hands back the executable as-is, without the
        first-call poisoned-entry fallback."""
        key = self._key(args)
        sig = key_digest(key)
        with self._lock:
            if sig not in self._compiled:
                self.ensure(*args)
            return self._compiled[sig]

    def __call__(self, *args):
        key = self._key(args)
        sig = key_digest(key)
        with self._lock:
            compiled = self._compiled.get(sig)
            if compiled is None:
                self.ensure(*args)
                compiled = self._compiled[sig]
            from_disk = sig not in self._verified
        try:
            out = compiled(*args)
        except Exception:
            if not from_disk:
                raise
            # poisoned disk entry survived the load checks but not the
            # call: recompile (overwriting the entry) and retry once
            _note("poisoned")
            with self._lock:
                self._compiled.pop(sig, None)
                compiled = self._cold(key, args, sig)
            out = compiled(*args)
        if from_disk:
            with self._lock:
                self._verified.add(sig)
        return out


def measure_roundtrip_ms(compiled):
    """The warm-start cost of one executable, measured in-process: the
    serialize -> deserialize_and_load round trip a restarted process pays
    instead of an XLA compile.  The bench telemetry block reports this as
    ``warm_compile_ms`` next to the cold ``compile_ms``.  None when the
    executable does not serialize."""
    try:
        from jax.experimental import serialize_executable as _se

        payload = pickle.dumps(_se.serialize(compiled))
        t0 = time.perf_counter()
        _load_on(_exec_devices(compiled), pickle.loads(payload))
        return (time.perf_counter() - t0) * 1e3
    except Exception:
        return None


# ----------------------------------------------------- pre-compilation --

_PRECOMPILERS = []                   # [(name, callable)]
_PRECOMPILE_LOCK = threading.Lock()
_PRECOMPILE_THREAD = None


def register_precompiler(fn, name=None):
    """Register a callable run (on a background daemon thread) after every
    committed checkpoint.  It should route its compiles through
    ``WarmCallable.ensure`` / the store so the work is idempotent — an
    already-published entry costs one digest + stat lookup.  Returns
    ``fn`` so it can be used as a decorator."""
    with _PRECOMPILE_LOCK:
        _PRECOMPILERS.append((name or getattr(fn, "__name__",
                                              "precompiler"), fn))
    return fn


def clear_precompilers():
    global _PRECOMPILE_THREAD
    with _PRECOMPILE_LOCK:
        del _PRECOMPILERS[:]
        _PRECOMPILE_THREAD = None


def precompile_thread():
    """The live background pre-compile thread, or None (tests and the
    monitor_overhead probe join on it)."""
    with _PRECOMPILE_LOCK:
        t = _PRECOMPILE_THREAD
    return t if t is not None and t.is_alive() else None


def notify_commit(step=None):
    """Checkpoint-commit hook (ft/ckpt.TrainStateWriter): kick the
    registered pre-compilers on a daemon thread.  Single-flight — a commit
    landing while the previous sweep still compiles is coalesced (the
    sweep is idempotent, the NEXT commit re-runs it).  No-op without
    registered pre-compilers or an active store."""
    global _PRECOMPILE_THREAD
    if store() is None:
        return None
    with _PRECOMPILE_LOCK:
        jobs = list(_PRECOMPILERS)
        if not jobs:
            return None
        if _PRECOMPILE_THREAD is not None and _PRECOMPILE_THREAD.is_alive():
            return _PRECOMPILE_THREAD

        def _run():
            for name, fn in jobs:
                if _SHUTTING_DOWN:
                    return
                try:
                    n = fn()
                    _note("precompiled", int(n) if n else 1)
                except Exception as e:       # noqa: BLE001 — background QoS
                    _note("precompile_errors")
                    warnings.warn("warm pre-compiler %r failed: %r"
                                  % (name, e))

        _arm_atexit()
        t = threading.Thread(target=_run, daemon=True,
                             name="warm-precompile")
        _PRECOMPILE_THREAD = t
        t.start()
        return t


def topology_worlds(world):
    """The world sizes an elastic resize can restart into from ``world``:
    post-shrink (``world - 1``, the launcher's ``--elastic_shrink`` step)
    and post-grow (``world + 1``)."""
    world = int(world)
    out = []
    if world > 1:
        out.append(world - 1)
    out.append(world + 1)
    return out


def topology_precompiler(build_for_world, world, worlds=None, label=None):
    """A ready-made pre-compiler for elastic resizes: for each target world
    size (default ``topology_worlds(world)``), call
    ``build_for_world(target_world)`` — which should return a
    ``(WarmCallable, args)`` pair whose key/avals come from the
    parallel/rules.py specs for THAT world — and ``ensure`` it into the
    store.  A world the current process cannot compile for (not enough
    local devices to build the mesh) is skipped with a warning, not an
    error.  Register the result::

        warm.register_precompiler(
            warm.topology_precompiler(build_for_world, world=fleet_world()))
    """
    targets = list(worlds) if worlds is not None else topology_worlds(world)

    def _precompile():
        done = 0
        for w in targets:
            try:
                built = build_for_world(w)
            except Exception as e:       # noqa: BLE001 — undersized host etc.
                warnings.warn(
                    "warm topology pre-compile: world %d not buildable "
                    "here (%r); it will compile cold if it ever runs" % (w, e))
                continue
            if built is None:
                continue
            wc, args = built
            if wc.ensure(*args) != "cached":
                done += 1
        return done

    _precompile.__name__ = label or "topology_precompiler"
    return _precompile
