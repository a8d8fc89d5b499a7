"""Measure a cell as the driver does: sets of runs of the manifest's
command, each run a new process with another seed, and for each end-to-end
metric the set's median and spread (distance between the quartiles over the
median).  The parent never touches JAX, so each child has the chip.

    python3 benchmark/tools/run_sets.py --workload <cell> [--sets 2] [--runs 6]
        [--seed0 100] [--out chiprun_out/<cell>.sets.json]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--out")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    sets, seed = [], args.seed0
    for s in range(args.sets):
        runs = []
        for _ in range(args.runs):
            cmd = m["command"] + ["--workload", args.workload, "--seed",
                                  str(seed), "--seconds",
                                  str(m["run_seconds"]), "--trace", "0"]
            t0 = time.time()
            done = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            lines = done.stdout.strip().splitlines()
            if done.returncode != 0 or not lines:
                print(done.stdout[-2000:], done.stderr[-4000:])
                sys.exit("run with seed %d failed (rc %d)"
                         % (seed, done.returncode))
            out = json.loads(lines[-1])
            ref = next((json.loads(l.split(": ", 1)[1]) for l in lines
                        if l.startswith("reference: ")), {})
            parts = [l for l in lines if l.startswith(("start: ", "setup: "))]
            # "window: ...; <rate> units/s over the whole window"
            whole = next((float(l.rsplit("; ", 1)[1].split()[0])
                          for l in lines if l.startswith("window: ")), None)
            runs.append({"seed": seed, "correct": out["correct"],
                         "setup_parts": " | ".join(parts),
                         "wall_s": time.time() - t0,
                         "reference_error": ref.get("relative_error"),
                         "window_rate": whole,
                         **{k: v["value"] for k, v in out["metrics"].items()}})
            print(json.dumps(runs[-1]), flush=True)
            seed += 1
        sets.append(runs)
    names = [e["name"] for e in m["end_to_end"] if e["name"] in sets[0][0]]
    names.append("window_rate")     # units over the whole window, beside it
    summary = {}
    for name in names:
        summary[name] = [{"median": statistics.median(r[name] for r in runs),
                          "spread": spread([r[name] for r in runs])}
                         for runs in sets]
        print(name, json.dumps(summary[name]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)) or ".",
                    exist_ok=True)
        with open(os.path.join(ROOT, args.out), "w") as f:
            json.dump({"workload": args.workload, "sets": sets,
                       "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
