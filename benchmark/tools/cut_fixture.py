"""Cut a fixture of a few hundred events out of a dumped trace, and work
out what the reduction has to give on it by an independent, brute-force
route: times are rounded to whole nanoseconds, every operation is painted
into an array with one cell per nanosecond, and the paint is counted.

    python3 benchmark/tools/cut_fixture.py <dump.json.gz> <start_ms> <length_ms> <out.json> [note]

The cut keeps, of every device plane, the events of ``XLA Ops`` that lie
wholly inside [start, start + length) and are no control flow, one synthetic
``XLA Modules`` event spanning the cut, and the host's ``bench.*`` spans
that overlap it.
"""

import gzip
import json
import sys

import numpy as np

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")
KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv",
           "layer_norm_fwd", "layer_norm_bwd")


def main(src, start_ms, length_ms, out, note=""):
    with gzip.open(src, "rt") as f:
        trace = json.load(f)
    lo = int(float(start_ms) * 1e6)
    hi = lo + int(float(length_ms) * 1e6)
    planes, paint = [], []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:TPU:"):
            spans = [[n, int(s) - lo, int(d)] for line in plane["lines"]
                     for n, s, d in line["events"]
                     if n.startswith("bench.") and s < hi and s + d > lo]
            planes.append({"name": plane["name"], "lines": [
                {"name": "python", "events": spans}]})
            continue
        ops = next(l["events"] for l in plane["lines"]
                   if l["name"] == "XLA Ops")
        inside = [[n, int(round(s)), int(round(d))] for n, s, d in ops
                  if s >= lo and s + d <= hi]
        leaves = [e for e in inside
                  if e[0].split(".")[0] not in ("while", "conditional",
                                                "call")]
        events = [[n, s - lo, d] for n, s, d in leaves]
        planes.append({"name": plane["name"], "lines": [
            {"name": "XLA Ops", "events": events},
            {"name": "XLA Modules", "events": [["cut", 0, hi - lo]]}]})
        paint.append(events)

    n = hi - lo
    busy, coll, exposed = [], [], []
    kernel = {k: [] for k in KERNELS}
    for events in paint:
        any_op = np.zeros(n, bool)
        other = np.zeros(n, bool)
        in_coll = np.zeros(n, bool)
        per = {k: 0 for k in KERNELS}
        starts = {}
        for name, s, d in sorted(events, key=lambda e: e[1]):
            any_op[s:s + d] = True
            kind = next((c for c in COLLECTIVE if c in name), None)
            if kind is None:
                other[s:s + d] = True
                base = name.rsplit(".", 1)[0] if name.rsplit(
                    ".", 1)[-1].isdigit() else name
                if base in per:
                    per[base] += d
            elif "-start" in name:
                starts.setdefault(kind, []).append(s)
            elif "-done" in name and starts.get(kind):
                in_coll[starts[kind].pop(0):s + d] = True
            else:
                in_coll[s:s + d] = True
        busy.append(int(any_op.sum()))
        coll.append(int(in_coll.sum()))
        exposed.append(int((in_coll & ~other).sum()))
        for k in KERNELS:
            kernel[k].append(per[k])
    mean = lambda xs: sum(xs) / len(xs) / 1e9  # noqa: E731
    expect = {"devices": len(paint), "window_s": n / 1e9,
              "busy_s": mean(busy), "collective_s": mean(coll),
              "collective_exposed_s": mean(exposed),
              "kernel_s": {k: mean(v) for k, v in kernel.items()
                           if sum(v) > 0},
              "how": "painted at 1 ns by benchmark/tools/cut_fixture.py, "
                     "per device: busy %s, collective %s, exposed %s ns"
                     % (busy, coll, exposed)}
    with open(out, "w") as f:
        json.dump({"note": note, "source": src, "cut_ms": [float(start_ms),
                                                          float(length_ms)],
                   "expect": expect, "trace": {"planes": planes}}, f)
    print(json.dumps(expect), sum(len(e) for e in paint), "events")


if __name__ == "__main__":
    main(*sys.argv[1:])
