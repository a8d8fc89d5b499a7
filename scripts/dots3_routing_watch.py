"""Whether the routing of ``dots3_note_prev.s8192_scan`` holds its first
static capacity while it trains: the (token, expert) pairs that meet a held
expert, a sparse layer, at the seeded weights and every ten steps through
fifty, on the cell's own trainer and staged batches; beside them the (query,
key) pairs the first layer's indexer selects.

    chiprun -- python3 scripts/dots3_routing_watch.py [seed] [steps]

The router's selection biases are moved against the load (5e-5 a step) and
ONE share alone trains its routers toward the experts it holds (ROADMAP's
lesson of PRs 52 and 58: a share's rows can drift past the headroom); the
first capacity is what ``moe._held_capacities`` gives over the 2,048 rows
uniform routing brings.  Prints one JSON line a reading and the losses
between; writes ``chiprun_out/pr63/dots3_routing_watch_<seed>.json``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from paddle_tpu import compile_cache  # noqa: E402
from paddle_tpu.parallel import decoder, moe  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

NAME, CELL = "dots3_note_prev", "dots3_note_prev.s8192_scan"


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
    held = jax.jit(lambda p, ids: decoder.forward(p, ids, cfg)[1]["rows_held"])
    selected = jax.jit(lambda p, ids: decoder._mass_selected(p, ids, cfg))
    out = {"seed": seed, "platform": jax.devices()[0].platform,
           "pairs_a_layer": pairs, "capacities": list(caps),
           "uniform": pairs * cfg.experts_here // cfg.n_experts,
           "readings": []}
    done, per = 0, len(made)
    while True:
        params = trainer.state["params"]
        rows = [[int(n) for n in np.asarray(held(params, b["ids"]))]
                for b in made]
        mass, chosen = (float(x) for x in selected(params, made[0]["ids"]))
        out["readings"].append({
            "step": done, "rows_held": rows,
            "largest_over_capacity": max(map(max, rows)) / caps[0],
            "pairs_selected_first_layer": int(chosen),
            "mass_selected_first_layer": mass})
        print(json.dumps(out["readings"][-1]), flush=True)
        if done >= steps:
            break
        for _ in range(10 // per):
            losses = np.asarray(trainer.run_steps(staged, float(config["lr"])))
            done += per
        print(json.dumps({"step": done, "loss": float(losses[-1])}),
              flush=True)
    path = os.path.join(ROOT, "chiprun_out", "pr63",
                        "dots3_routing_watch_%d.json" % seed)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
