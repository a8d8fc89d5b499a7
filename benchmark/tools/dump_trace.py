"""Look at a trace by hand: planes, lines, event counts, the names with the
most time, and the neutral form written out (gzipped JSON) for cutting
fixtures.

    python3 benchmark/tools/dump_trace.py <trace dir> <out prefix>
"""

import collections
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace_reduce as tr  # noqa: E402


def main(trace_dir, out_prefix):
    from jax.profiler import ProfileData

    path = tr.find_xplane(trace_dir)
    print("xplane:", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print("  LINE %r: %d events, %.3f..%.3f ms"
                  % (line.name, len(events), lo / 1e6, hi / 1e6))
            if plane.name.startswith("/device:TPU:0"):
                acc = collections.Counter()
                for e in events:
                    acc[e.name] += e.duration_ns
                for name, ns in acc.most_common(12):
                    print("      %-60s %.3f ms" % (name[:60], ns / 1e6))
                e = events[len(events) // 2]
                print("      stats of %r: %s" % (e.name, dict(e.stats)))
    neutral = tr.load_xplane(path)
    os.makedirs(os.path.dirname(out_prefix) or ".", exist_ok=True)
    with gzip.open(out_prefix + ".json.gz", "wt") as f:
        json.dump(neutral, f)
    r = tr.Reduced(neutral)
    print("window_s %.6f busy_s %.6f collective_s %.6f exposed_s %.6f"
          % (r.window_s, r.busy_s, r.collective_s, r.collective_exposed_s))
    print("top ops:", json.dumps(r.top_ops(10)))
    print("top gaps:", json.dumps(r.top_gaps(5)))
    print("host spans:", len(r.host_spans), r.host_spans[:6])


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
