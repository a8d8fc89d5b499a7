"""Whether the routing of ``keye_vl2_30b_a3b.s16384_scan`` holds its first
static capacity while it trains: the (token, expert) pairs that meet a held
expert, a layer, at the seeded weights and every ten steps through fifty, on
the cell's own trainer and staged batches.

    chiprun -- python3 scripts/keye_vl2_routing_watch.py [seed] [steps]

The configuration carries no auxiliary coefficient and the router no
selection bias (ROADMAP's lesson of PRs 52 and 58: a share's rows can drift
past the headroom as the router trains); the first capacity is 1.25 x the
16,384 rows uniform routing brings (``moe._held_capacities``).  Prints one
JSON line a reading and the losses between; writes
``chiprun_out/pr61/keye_vl2_routing_watch_<seed>.json``."""

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

NAME, CELL = "keye_vl2_30b_a3b", "keye_vl2_30b_a3b.s16384_scan"


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
    out = {"seed": seed, "platform": jax.devices()[0].platform,
           "pairs_a_layer": pairs, "capacities": list(caps),
           "uniform": pairs * cfg.experts_here // cfg.n_experts,
           "readings": []}
    done, per = 0, len(made)
    while True:
        rows = [[int(n) for n in np.asarray(held(trainer.state["params"],
                                                 b["ids"]))] for b in made]
        out["readings"].append({"step": done, "rows_held": rows,
                                "largest_over_capacity":
                                    max(map(max, rows)) / caps[0]})
        print(json.dumps(out["readings"][-1]), flush=True)
        if done >= steps:
            break
        for _ in range(10 // per):
            losses = np.asarray(trainer.run_steps(staged, float(config["lr"])))
            done += per
        print(json.dumps({"step": done, "loss": float(losses[-1])}),
              flush=True)
    path = os.path.join(ROOT, "chiprun_out", "pr61",
                        "keye_vl2_routing_watch_%d.json" % seed)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
