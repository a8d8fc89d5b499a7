"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, loads its configuration and traffic
files by name, imports the driver the traffic names, and prints as the last
line of standard output one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``; ``breakdown`` when traced).  Exits
non-zero and prints no result off a TPU, on fewer chips than the cell
needs, on a device the peaks table does not list, or where the program is
not there to be measured.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import manifest as mf
    from benchmark.harness.device import NoChip, check_devices
    from benchmark.harness.peaks import UnlistedDevice

    m = mf.load(ROOT)
    cell = mf.cell(m, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmark: no program to measure under %s" % ROOT,
              file=sys.stderr)
        return 3

    import jax

    t_jax = time.perf_counter()
    try:
        used, peaks = check_devices(jax.devices(), cell["chips"])
    except (NoChip, UnlistedDevice) as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 2

    t_devices = time.perf_counter()
    from paddle_tpu import compile_cache

    print("compile cache: %s" % compile_cache.place(), flush=True)
    # every program of a run is in the cache after the cell's first run,
    # the sub-second ones too (JAX's default floor of 1 s would re-pay them
    # in every run where the environment names the directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from benchmark.harness.cellrun import run_cell

    print("start: import jax %.3f s, devices %.3f s, program and cache %.3f s"
          % (t_jax - T_START, t_devices - t_jax,
             time.perf_counter() - t_devices), flush=True)
    out = run_cell(ROOT, m, args.workload, args.seed, args.seconds,
                   args.trace, T_START, used, all_devices=jax.devices(),
                   peaks=peaks)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
