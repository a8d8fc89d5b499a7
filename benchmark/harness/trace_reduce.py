"""From a profiler trace to device metrics.

The trace is first brought into a neutral form, so that the reduction can be
checked on small recorded fixtures (``benchmark/tests/fixtures``)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

What the planes of a v5e trace are (looked at by hand, PERF.md section 3):
one plane ``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one
event per executed HLO instruction (Pallas kernels under their ``name=``,
fusions as XLA names them, control flow such as ``while`` as an event that
CONTAINS its body's events) and whose line ``XLA Modules`` holds one event
per executed program; ``/host:CPU`` holds the host threads, where the
benchmark's ``bench.*`` annotations sit on the same clock.

Definitions, per device plane and then averaged over the planes:

- operations: the events of ``XLA Ops`` but control flow (``while``,
  ``conditional``, ``call``), whose event contains its body's and is no
  operation itself.  (Containment in time does not tell them apart: a
  zero-length ``copy-done`` stamped inside a 2 ms all-reduce made that a
  "container" on one of four chips.)
- window: per plane, from the start of the first program of ``XLA Modules``
  to the end of the last (without that line, the extent of the
  events); the window is the stretch all planes share.  Events are clipped
  to it.
- busy: the union of the operation intervals.  idle = window - busy.
- per-name time: the sum of each operation's clipped duration by the name
  the trace gives.
- collective intervals: operations whose name says all-reduce, all-gather,
  reduce-scatter, all-to-all or collective-permute; an asynchronous
  ``-start`` is joined with the ``-done`` that follows it into one interval
  from the start of the one to the end of the other, and the line ``Async
  XLA Ops``, where the trace draws such a pair as one event, is read too.
- exposed collective time: the part of the collective intervals during
  which no other operation runs on that device.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.\d+)?$")
OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


# -- loading ----------------------------------------------------------------

def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path):
    """The neutral form of an ``.xplane.pb``: every line of the device
    planes, and of the host plane only the benchmark's own spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if (device and not is_control_flow(e.name))
                      or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name):
    """The trace names a device operation by its whole HLO line,
    ``%fusion.12 = bf16[...] fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_control_flow(name):
    """By the opcode of the HLO line where the trace gives one, else by
    the instruction's name."""
    head, eq, rest = name.partition(" = ")
    m = OPCODE.search(" " + rest) if eq else None
    if m:
        return m.group(1) in ("while", "conditional", "call")
    return CONTROL_FLOW.match(head.lstrip("%")) is not None


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b):
    """The parts of the merged intervals ``a`` that the merged intervals
    ``b`` do not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- one device plane ---------------------------------------------------------

def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def operations(events):
    """(name, start, end) of the events that are operations, in time
    order."""
    return sorted(((n, s, s + d) for n, s, d in events
                   if not CONTROL_FLOW.match(n)), key=lambda e: e[1])


def collective_intervals(ops):
    """One interval per collective: a plain event's own, or from an
    asynchronous ``-start`` to the end of the next ``-done`` of its kind."""
    out, open_starts = [], {}
    for name, lo, hi in ops:
        m = COLLECTIVE.search(name)
        if not m:
            continue
        kind = m.group(1)
        if "-start" in name:
            open_starts.setdefault(kind, []).append(lo)
        elif "-done" in name and open_starts.get(kind):
            out.append((open_starts[kind].pop(0), hi))
        else:
            out.append((lo, hi))
    return out


def modules_window(planes):
    """The stretch that every plane recorded: the devices of one host start
    and stop recording tens of milliseconds apart (seen on four chips)."""
    los, his = [], []
    for p in planes:
        spans = [(s, s + d) for _, s, d in _line(p, MODULES_LINE)] or [
            (lo, hi) for _, lo, hi in operations(_line(p, OPS_LINE))]
        if spans:
            los.append(min(s for s, _ in spans))
            his.append(max(e for _, e in spans))
    if not los or max(los) >= min(his):
        return None
    return max(los), min(his)


def reduce_plane(plane, window):
    lo, hi = window
    ops = [(n, max(a, lo), min(b, hi))
              for n, a, b in operations(_line(plane, OPS_LINE))
              if min(b, hi) > max(a, lo)]
    busy = union((a, b) for _, a, b in ops)
    by_name, count = {}, {}
    for n, a, b in ops:
        by_name[n] = by_name.get(n, 0.0) + (b - a)
        count[n] = count.get(n, 0) + 1
    coll = union(clip(
        collective_intervals(ops)
        + [(s, s + d) for n, s, d in _line(plane, ASYNC_LINE)
           if COLLECTIVE.search(n)], lo, hi))
    other = union((a, b) for n, a, b in ops if not COLLECTIVE.search(n))
    return {"name": plane["name"], "busy_ns": total(busy),
            "gaps": subtract([(lo, hi)], busy), "by_name": by_name,
            "count": count,
            "collective_ns": total(coll),
            "collective_exposed_ns": total(subtract(coll, other)),
            "events": len(ops)}


# -- the whole trace ----------------------------------------------------------

class Reduced:
    """What the per-layer metrics read: seconds, averaged over devices."""

    def __init__(self, trace):
        planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
        self.window = modules_window(planes)
        self.devices = ([reduce_plane(p, self.window) for p in planes]
                        if self.window else [])
        self.host_spans = [(n, s, s + d) for p in trace["planes"]
                           if p["name"] == HOST_PLANE
                           for line in p["lines"] for n, s, d in line["events"]
                           if n.startswith(SPAN_PREFIX)]

    def __bool__(self):
        return bool(self.devices) and any(d["events"] for d in self.devices)

    def _mean(self, key):
        return sum(d[key] for d in self.devices) / len(self.devices) / 1e9

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self):
        return self._mean("busy_ns")

    @property
    def collective_s(self):
        return self._mean("collective_ns")

    @property
    def collective_exposed_s(self):
        return self._mean("collective_exposed_ns")

    def _kernel(self, key, kernels):
        """Mean over devices of ``key`` over the events of these kernels:
        the trace names an instruction ``<name>`` or ``<name>.<n>``."""
        pat = re.compile(r"^(%s)(\.\d+)?$" % "|".join(map(re.escape, kernels)))
        return sum(v for d in self.devices for n, v in d[key].items()
                   if pat.match(n)) / len(self.devices)

    def seconds_of_kernels(self, kernels):
        return self._kernel("by_name", kernels) / 1e9

    def count_of_kernels(self, kernels):
        return self._kernel("count", kernels)

    def top_ops(self, k=10):
        acc = {}
        for d in self.devices:
            for n, ns in d["by_name"].items():
                acc[n] = acc.get(n, 0.0) + ns / len(self.devices) / 1e9
        return [[n, s] for n, s in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k=5):
        """The longest idle gaps over all devices, each named by the
        benchmark span that covers most of it."""
        gaps = sorted(((hi - lo, lo, hi) for d in self.devices
                       for lo, hi in d["gaps"]), reverse=True)[:k]
        out = []
        for dur, lo, hi in gaps:
            best, cover = "unattributed", 0.0
            for n, a, b in self.host_spans:
                c = min(b, hi) - max(a, lo)
                if c > cover:
                    best, cover = n, c
            out.append([best, dur / 1e9])
        return out
