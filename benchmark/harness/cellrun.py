"""One run of one cell: build, stage, warm up, check against the reference,
measure, reduce.  ``run.py`` calls this after the device check; the tests
call it on the CPU at tiny sizes (and print no device metric)."""

import json
import os
import statistics
import time

import numpy as np

from . import build, compiles, manifest as mf, trace_reduce, tracing
from .spans import Spans


class Ctx:
    """What a driver is handed."""

    trace_dir = None


SLICE_SECONDS = 1.0


def sustained_rate(marks, units_per_mark):
    """Units per second the run sustains: the window is cut at completion
    marks into slices of at least ``SLICE_SECONDS`` (a dispatch, where that
    is longer) and the MEDIAN of the slices' rates is taken -- the
    contract's "medians over the whole window".  2 of 14 host-fed runs lost
    1.3 s and 3.2 s of a 20 s window to one stall each (7 % and 16 % of
    units / window; my chip runs, PR 22); two such runs in a set of six,
    which that rate makes likely one set in five, read as a spread of
    several per cent where the bound is 1 %.  The median over some twenty
    slices leaves a lone stall out and still sees whatever slows half the
    slices; what it leaves out is reported as ``window_lost_share``, and
    units over the whole window go on an earlier line.  The stretch before
    the first mark, which starts on an idle device and an empty pipe,
    belongs to no slice.  None when the window holds no whole slice."""
    rates, start, n = [], marks[0] if marks else None, 0
    for t in marks[1:]:
        n += 1
        if t - start >= SLICE_SECONDS:
            rates.append(n * units_per_mark / (t - start))
            start, n = t, 0
    return statistics.median(rates) if rates else None


def check_reference(config, params, batch0, first_loss):
    """The system's first loss against the plain reference's on the same
    weights and batch."""
    ref = mf.module("reference", config["reference"])
    ref_loss = float(ref.loss(params, batch0, config["model"]))
    err = abs(first_loss - ref_loss) / max(abs(ref_loss), 1e-12)
    return {"system_loss": first_loss, "reference_loss": ref_loss,
            "relative_error": err, "tolerance": ref.TOLERANCE,
            "ok": bool(np.isfinite(first_loss) and err <= ref.TOLERANCE)}


def run_cell(root, m, cell_name, seed, seconds, trace, t_start, devices,
             all_devices=None, peaks=None, say=print):
    """Returns the last line's object (without printing it) and writes the
    run's details to ``<benchmark>/out/<cell>/``."""
    from .device import device_facts

    cell = mf.cell(m, cell_name)
    entry = mf.config_entry(m, cell["config"])
    config = mf.read_json(root, entry["file"])
    bench_dir = os.path.dirname(os.path.dirname(entry["file"]))
    traffic = mf.read_json(root, bench_dir, "traffic", cell_name + ".json")
    driver = mf.module("drivers", traffic["driver"])

    ctx = Ctx()
    ctx.config, ctx.traffic, ctx.seed = config, traffic, int(seed)
    ctx.seconds, ctx.trace, ctx.spans = float(seconds), bool(trace), Spans()
    ctx.dims, ctx.lr = build.cell_dims(config, traffic), float(config["lr"])
    ctx.devices, ctx.say = list(devices), say
    ctx.out_dir = os.path.join(root, bench_dir, "out", cell_name)
    os.makedirs(ctx.out_dir, exist_ok=True)

    stamps = compiles.count_backend_compiles()
    with ctx.spans.span("bench.build"):
        ctx.trainer = build.build_trainer(config, traffic, ctx.seed,
                                          ctx.devices)
        # a host copy for the reference: the first step donates the state
        params0 = _host_copy(ctx.trainer.state["params"])
    st = driver.prepare(ctx)
    with ctx.spans.span("bench.reference"):
        check = check_reference(config, params0, st["batch0"],
                                st["first_loss"])
    del params0
    say("reference: %s" % json.dumps(check))
    setup_s = time.perf_counter() - t_start
    say("setup: %.3f s; %s" % (setup_s, ", ".join(
        "%s %.3f" % (n[6:], t1 - t0) for n, t0, t1, _ in ctx.spans.records
        if n in ("bench.build", "bench.stage", "bench.warmup",
                 "bench.reference"))))

    run = driver.measure(ctx, st)

    window_s = run["t1"] - run["t0"]
    per_step = build.units_per_step(config, ctx.dims)
    recompiles = sum(1 for t in stamps if run["t0"] <= t <= run["t1"])
    window_rate = run["steps"] * per_step / window_s
    throughput = sustained_rate(run["marks"], run["steps_per_mark"] * per_step)
    if throughput is None:          # a window too short for one slice
        throughput = window_rate
    device = device_facts(all_devices or devices, ctx.devices)
    say("memory_stats: %s" % json.dumps(ctx.devices[0].memory_stats()))
    correct = bool(check["ok"] and run["losses_finite"] and recompiles == 0)
    say("window: %.4f s, %d dispatches, %d steps, %d step samples (longest "
        "%.4f ms), last loss %.6g, recompiles %d; %.6f units/s over the "
        "whole window"
        % (window_s, run["attempted"], run["steps"], len(run["step_ms"]),
           max(run["step_ms"], default=0.0), run["last_loss"], recompiles,
           window_rate))
    values = {"train_throughput": throughput,
              "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
              "setup_s": setup_s}
    out = {"correct": correct, "attempted": run["attempted"],
           "failed": run["failed"], "device": device}
    details = {"cell": cell_name, "seed": ctx.seed, "reference": check,
               "window_s": window_s, "window_rate": window_rate,
               "step_ms": run["step_ms"], "end_to_end": values,
               "spans": ctx.spans.records}
    if not trace:
        out["metrics"] = _named(m, "end_to_end", cell_name, values)
    else:
        facts = {"chips": cell["chips"], "config": config, "traffic": traffic,
                 "dims": ctx.dims, "peaks": peaks, "t0": run["t0"],
                 "t1": run["t1"], "window_s": window_s,
                 "throughput": throughput, "window_rate": window_rate,
                 "step_ms": run["step_ms"], "recompiles": recompiles,
                 "say": say}
        reduced = _reduce_trace(ctx)
        per_layer = {}
        for e in mf.metrics_of(m, "per_layer", cell_name):
            got = mf.module("layer_metrics", e["name"]).read(
                reduced, ctx.spans, run.get("counters", {}), facts)
            if got is not None:
                per_layer[e["name"]] = got
        out["metrics"] = _named(m, "per_layer", cell_name, per_layer)
        if reduced:
            out["device"] = dict(device, busy_s=reduced.busy_s,
                                 window_s=reduced.window_s)
            out["breakdown"] = {"device_ops": reduced.top_ops(10),
                                "idle_gaps": reduced.top_gaps(5)}
        details["per_layer"] = per_layer
    with open(os.path.join(ctx.out_dir, "last_run.json"), "w") as f:
        json.dump(details, f)
    return out


def _reduce_trace(ctx):
    """The reduced trace of the run's traced part, or None without one."""
    xplane = ctx.trace_dir and trace_reduce.find_xplane(ctx.trace_dir)
    if not xplane:
        return None
    reduced = trace_reduce.Reduced(trace_reduce.load_xplane(xplane))
    if reduced and not reduced.host_spans:
        reduced.host_spans = tracing.anchored(ctx)
    return reduced


def _named(m, kind, cell_name, values):
    return {e["name"]: {"value": float(values[e["name"]]), "unit": e["unit"]}
            for e in mf.metrics_of(m, kind, cell_name) if e["name"] in values}


def _host_copy(tree):
    import jax

    return jax.tree.map(np.asarray, tree)
