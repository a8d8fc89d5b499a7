"""Whether the routing of ``sdar_30b_a3b_chat.s8192_scan`` holds its first
static capacity while it trains: the (row, expert) pairs that meet a held
expert, a layer (BOTH copies' rows are routed: 131,072 pairs a layer), and
the busiest expert over the mean (``load_max_over_mean``, the largest over
layers), at the seeded weights and every ten steps through fifty, on the
cell's own trainer and staged batches; the masked tokens and the head's row
blocks of each staged batch; and first of all the kernels' call counters of
one trace of the step's loss AND its gradient (``kernel_calls``: ``fused=1``
where a ``supported(shape)`` took the kernel; the flash backward's is traced
under ``jax.grad`` alone).

    chiprun -- python3 scripts/sdar_routing_watch.py [seed] [steps]

The configuration carries no auxiliary coefficient and the router no
selection bias (ROADMAP's lesson of PRs 52 and 58: a share's rows can drift
past the headroom as the router trains); the first capacity is 1.25 x the
32,768 rows uniform routing brings (``moe._held_capacities``).  Prints one
JSON line a reading and the losses between; writes
``chiprun_out/pr71/sdar_routing_watch_<seed>.json``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from paddle_tpu import compile_cache, monitor  # noqa: E402
from paddle_tpu.parallel import decoder, moe, transformer  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

NAME, CELL = "sdar_30b_a3b_chat", "sdar_30b_a3b_chat.s8192_scan"
OUT = os.path.join(ROOT, "chiprun_out", "pr71")


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
    staged = stack_batches(trainer.mesh, decoder.batch_specs(cfg), made)
    pairs = 2 * made[0]["ids"].size * cfg.experts_per_token
    caps = moe._held_capacities(pairs, cfg.experts_here, cfg.n_experts)

    def read(p, batch):
        aux = decoder.forward(p, decoder.noised_rows(batch, cfg)[0], cfg)[1]
        return aux["rows_held"], aux["load_max_over_mean"]

    read = jax.jit(read)
    # which branch every ``supported(shape)`` took as the step's loss and
    # gradient are traced HERE, on the chip
    mon = monitor.enable(os.path.join(OUT, "mon"), flight=False)
    try:
        mon.registry.reset()
        jax.eval_shape(jax.value_and_grad(decoder.make_loss_fn(cfg)),
                       trainer.state["params"], made[0])
        calls = {"%s{%s}" % (r["name"][len("monitor.kernels."):], ",".join(
            "%s=%s" % kv for kv in sorted(r["labels"].items()))): r["value"]
            for r in mon.registry.snapshot()
            if r["name"].startswith("monitor.kernels.")}
    finally:
        monitor.disable()
    print(json.dumps({"kernel_calls": calls}), flush=True)
    masked = [int((b["u"] < np.repeat(b["t"], cfg.block_diffusion, -1)).sum())
              for b in made]
    block = transformer.head_row_block(made[0]["ids"].size)
    out = {"seed": seed, "platform": jax.devices()[0].platform,
           "pairs_a_layer": pairs, "capacities": list(caps),
           "uniform": pairs * cfg.experts_here // cfg.n_experts,
           "masked_tokens": masked,
           "head_blocks": [-(-m // block) for m in masked],
           "kernel_calls": calls, "readings": []}
    print(json.dumps({k: out[k] for k in (
        "pairs_a_layer", "capacities", "uniform", "masked_tokens",
        "head_blocks")}), flush=True)
    done, per = 0, len(made)
    while True:
        params = trainer.state["params"]
        got = [read(params, b) for b in made]
        rows = [[int(n) for n in np.asarray(g[0])] for g in got]
        out["readings"].append({
            "step": done, "rows_held": rows,
            "largest_over_capacity": max(map(max, rows)) / caps[0],
            "load_max_over_mean": max(float(np.max(g[1])) for g in got)})
        print(json.dumps(out["readings"][-1]), flush=True)
        if done >= steps:
            break
        for _ in range(10 // per):
            losses = np.asarray(trainer.run_steps(staged, float(config["lr"])))
            done += per
        print(json.dumps({"step": done, "loss": float(losses[-1])}),
              flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sdar_routing_watch_%d.json" % seed),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
