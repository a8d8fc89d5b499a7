"""Whether the routing of ``solar_open2_250b.s4096_scan`` holds its first
static capacity while it trains: the (token, expert) pairs that meet a held
expert, a layer, and the busiest expert over the mean
(``load_max_over_mean``, the largest over layers), at the seeded weights and
every ten steps through fifty, on the cell's own trainer and staged batches;
beside them the share of the first KDA layer's writes with ``beta`` > 1,
and first of all the kernels' call counters of one trace of the step's loss
(``kernel_calls``: ``fused=1`` where a ``supported(shape)`` took the kernel).

    chiprun -- python3 scripts/solar_open2_routing_watch.py [seed] [steps]

The router's selection biases are moved against the load (5e-3 a step) and
ONE share alone trains its routers toward the experts it holds (ROADMAP's
lesson of PRs 52 and 58: a share's rows can drift past the headroom); the
first capacity is what ``moe._held_capacities`` gives over the 1,024 rows
uniform routing brings 10 of 320 experts.  Prints one JSON line a reading
and the losses between; writes
``chiprun_out/pr67/solar_open2_routing_watch_<seed>.json``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from paddle_tpu import compile_cache, monitor  # noqa: E402
from paddle_tpu.parallel import decoder, moe  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

NAME, CELL = "solar_open2_250b", "solar_open2_250b.s4096_scan"


def main(seed=0, steps=50):
    seed, steps = int(seed), int(steps)
    compile_cache.place()
    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    trainer = build.build_trainer(config, traffic, seed, jax.devices()[:1])
    cfg = trainer.cfg
    dims = build.cell_dims(config, traffic)
    made = [batches.host_batch(config["batch_fields"], dims, seed, i)
            for i in range(traffic["staged_batches"])]
    staged = stack_batches(trainer.mesh, decoder.BATCH_SPECS, made)
    pairs = made[0]["ids"].size * cfg.experts_per_token
    caps = moe._held_capacities(pairs, cfg.experts_here, cfg.n_experts)

    def read(p, ids):
        aux = decoder.forward(p, ids, cfg)[1]
        return aux["rows_held"], aux["load_max_over_mean"], \
            decoder.probe(p, ids, cfg)["kda_write_over_one_share"]

    read = jax.jit(read)
    # which branch every ``supported(shape)`` took as the step's loss is
    # traced HERE, on the chip: ``monitor.kernels.<kernel>_calls{labels}``
    mon = monitor.enable(os.path.join(ROOT, "chiprun_out", "pr67", "mon"),
                         flight=False)
    try:
        mon.registry.reset()
        jax.eval_shape(decoder.make_loss_fn(cfg), trainer.state["params"],
                       {"ids": made[0]["ids"]})
        calls = {"%s{%s}" % (r["name"][len("monitor.kernels."):], ",".join(
            "%s=%s" % kv for kv in sorted(r["labels"].items()))): r["value"]
            for r in mon.registry.snapshot()
            if r["name"].startswith("monitor.kernels.")}
    finally:
        monitor.disable()
    print(json.dumps({"kernel_calls": calls}), flush=True)
    out = {"seed": seed, "platform": jax.devices()[0].platform,
           "pairs_a_layer": pairs, "capacities": list(caps),
           "uniform": pairs * cfg.experts_here // cfg.n_experts,
           "kernel_calls": calls, "readings": []}
    done, per = 0, len(made)
    while True:
        params = trainer.state["params"]
        got = [read(params, b["ids"]) for b in made]
        rows = [[int(n) for n in np.asarray(g[0])] for g in got]
        out["readings"].append({
            "step": done, "rows_held": rows,
            "largest_over_capacity": max(map(max, rows)) / caps[0],
            "load_max_over_mean": max(float(np.max(g[1])) for g in got),
            "kda_write_over_one_share": float(got[0][2])})
        print(json.dumps(out["readings"][-1]), flush=True)
        if done >= steps:
            break
        for _ in range(10 // per):
            losses = np.asarray(trainer.run_steps(staged, float(config["lr"])))
            done += per
        print(json.dumps({"step": done, "loss": float(losses[-1])}),
              flush=True)
    path = os.path.join(ROOT, "chiprun_out", "pr67",
                        "solar_open2_routing_watch_%d.json" % seed)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
