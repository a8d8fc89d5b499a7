"""Device memory watermarks + owner attribution.

Three complementary sources, all best-effort (the CPU backend reports no
allocator stats; the TPU backend does):

- ``jax.live_arrays()`` — every live jax.Array's nbytes summed: what the
  FRAMEWORK is holding (parameters, optimizer moments, staged batches,
  HostPS cache slots).  Catches a leak of framework references even when
  the allocator stats are unavailable.
- ``device.memory_stats()`` — the backend allocator's ``bytes_in_use`` /
  ``peak_bytes_in_use``: what the CHIP is holding, including XLA temp
  buffers the framework never sees.  This is the number an HBM OOM is
  about.
- **MemScope owner attribution** (memscope.py) — the same live arrays
  classified by WHICH subsystem holds them (scope state, feed-pipe staged
  batches, HotRowCache slots, TrainLoop state, warm twins, registered
  owners) with an explicit ``unattributed`` remainder, plus host-side
  accounting (process RSS, HostPS resident tables, ShardPS replay logs).

Each sample sets gauges in the registry, each one an operator's to act on
(README, "Memory attribution (MemScope)", lists them with the action; the
sample's other numbers ride the ``memory`` event alone); ``*_peak`` gauges
only ratchet up
(``Gauge.set_max``) — the high-water mark survives between samples, so a
transient spike between two steps still shows if any sample lands on it.
The owner split lands in ``monitor.mem.owner_bytes{owner=}`` (the
``unattributed`` remainder is an owner of it) /
``monitor.mem.unattributed_frac`` and the per-device occupancy in
``monitor.mem.hbm_frac{device=}`` (+ the unlabeled ``hbm_frac_max`` the
fleet console reads), and the whole classified snapshot rides the
``memory`` timeline event — the input to ``trace_summary``'s owner
breakdown and its ``--max-hbm-frac`` / ``--max-unattributed-frac`` gates.
"""

__all__ = ["memory_snapshot", "sample_memory"]

# owner labels ever published to the owner_bytes gauge (stale-zeroing set)
_PUBLISHED_OWNERS = set()


def memory_snapshot():
    """{"live_bytes", "arrays", "devices": {dev: {bytes_in_use, ...}},
    "owners": {owner: bytes}, "hbm_frac": {dev: frac}, "host": {...}} —
    every field best-effort, absent keys mean the backend (or the owner
    registry) can't say."""
    import jax

    from . import memscope

    snap = {}
    dev_live = None
    try:
        attr = memscope.attribution()
        snap["arrays"] = attr["arrays"]
        snap["live_bytes"] = attr["live_bytes"]
        dev_live = attr.get("device_live_bytes")
        if attr["owners"]:
            snap["owners"] = attr["owners"]
    except Exception:
        try:
            arrs = jax.live_arrays()
            snap["arrays"] = len(arrs)
            snap["live_bytes"] = int(sum(getattr(a, "nbytes", 0)
                                         for a in arrs))
        except Exception:
            pass
    devs = {}
    try:
        for d in jax.devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            devs[str(d)] = {
                k: int(stats[k]) for k in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats
            }
    except Exception:
        pass
    if devs:
        snap["devices"] = devs
    try:
        # reuse the attribution walk's per-device totals: the estimated
        # headroom path must not pay a second live_arrays() sweep
        frac = memscope.hbm_frac(live=dev_live)
        if frac:
            snap["hbm_frac"] = frac
    except Exception:
        pass
    try:
        host = memscope.host_accounting()
        if host:
            snap["host"] = host
    except Exception:
        pass
    return snap


def sample_memory(registry, timeline=None):
    """Take one snapshot, update the watermark + attribution gauges,
    optionally emit a ``memory`` timeline event.  Returns the snapshot."""
    snap = memory_snapshot()
    if "live_bytes" in snap:
        registry.gauge("monitor.mem.live_bytes_peak").set_max(
            snap["live_bytes"])
    for dev, stats in snap.get("devices", {}).items():
        peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if peak is not None:
            registry.gauge("monitor.mem.device_bytes_peak",
                           device=dev).set_max(peak)
    owners = snap.get("owners")
    if owners:
        for owner, b in owners.items():
            registry.gauge("monitor.mem.owner_bytes", owner=owner).set(b)
        # an owner absent from THIS sample (unregistered, pipe died) must
        # read 0, not its stale last value, on a mid-run scrape — the
        # phase-gauge zeroing convention (session.record_step).  The
        # published-name set is process-level: registries are effectively
        # the process default here, and a spurious zero on a fresh
        # registry is harmless
        for o in _PUBLISHED_OWNERS - set(owners):
            registry.gauge("monitor.mem.owner_bytes", owner=o).set(0)
        _PUBLISHED_OWNERS.update(owners)
        total = snap.get("live_bytes") or sum(owners.values())
        if total:
            registry.gauge("monitor.mem.unattributed_frac").set(
                round(owners.get("unattributed", 0) / total, 4))
    fracs = snap.get("hbm_frac")
    if fracs:
        for dev, f in fracs.items():
            registry.gauge("monitor.mem.hbm_frac", device=dev).set(f)
        registry.gauge("monitor.mem.hbm_frac_max").set_max(
            max(fracs.values()))
    for k, v in (snap.get("host") or {}).items():
        registry.gauge("monitor.mem.host.%s" % k).set(v)
    if timeline is not None:
        timeline.emit("memory", **snap)
    return snap
