"""Whether the routing of ``kanana_2_30b_a3b.s8192_ep4`` holds the first
round of its exchange while it trains: a layer, the rows the chips SENT (the
(token, expert) pairs of the whole batch that left their chip), the rows the
fullest chip RECEIVED, the fullest destination of any chip against the rows a
round carries (``moe._exchange_capacity``: 15,360; uniform routing sends
12,288), the rounds past the first (``exchange_tier``: 0 under balance) and
the busiest expert over the mean on the GLOBAL batch
(``load_max_over_mean``), with the largest selection bias, at the seeded
weights and every ten steps through fifty, on the cell's own trainer, mesh
and staged batches; and first of all the kernels' call counters of one trace
of the step's loss AND its gradient on the mesh (``kernel_calls``: ``fused=1``
where a ``supported(shape)`` took the kernel).

    chiprun --chips 4 -- python3 scripts/kanana2_routing_watch.py [seed] [steps]

The selection bias is moved by the GLOBAL load and every expert reaches the
loss (each chip holds a quarter, all four are here): what PERF.md section 7
(ak) saw on a lone share, the routers learning to prefer the held experts,
has nothing to prefer here.  Prints one JSON line a reading and the losses
between; writes ``chiprun_out/pr73/kanana2_routing_watch_<seed>.json``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from paddle_tpu import compile_cache, monitor  # noqa: E402
from paddle_tpu.parallel import decoder, moe  # noqa: E402
from paddle_tpu.parallel.mesh import DP, local_shard_map  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

NAME, CELL = "kanana_2_30b_a3b", "kanana_2_30b_a3b.s8192_ep4"
OUT = os.path.join(ROOT, "chiprun_out", "pr73")
READ = ("rows_sent", "rows_received", "exchange_fullest", "exchange_tier",
        "load_max_over_mean")


def main(seed=0, steps=50):
    seed, steps = int(seed), int(steps)
    compile_cache.place()
    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    chips = traffic["mesh"]["dp"]
    trainer = build.build_trainer(config, traffic, seed,
                                  jax.devices()[:chips])
    cfg = trainer.cfg
    dims = build.cell_dims(config, traffic)
    made = [batches.host_batch(config["batch_fields"], dims, seed, i)
            for i in range(traffic["staged_batches"])]
    staged = stack_batches(trainer.mesh, decoder.batch_specs(cfg), made)
    pairs = made[0]["ids"].size // chips * cfg.experts_per_token
    cap = moe._exchange_capacity(pairs, chips)

    def read(p, ids):
        aux = decoder.forward(p, ids, cfg)[1]
        return {k: aux[k] for k in READ}

    read = jax.jit(local_shard_map(
        read, trainer.mesh, in_specs=(trainer.specs["params"], P(DP)),
        out_specs=P()))
    mon = monitor.enable(os.path.join(OUT, "mon"), flight=False)
    try:
        mon.registry.reset()
        jax.eval_shape(local_shard_map(
            jax.value_and_grad(decoder.make_loss_fn(cfg), has_aux=True),
            trainer.mesh,
            in_specs=(trainer.specs["params"], {"ids": P(DP)}),
            out_specs=((P(), P()), trainer.specs["params"])),
            trainer.state["params"], {"ids": made[0]["ids"]})
        calls = {"%s{%s}" % (r["name"][len("monitor.kernels."):], ",".join(
            "%s=%s" % kv for kv in sorted(r["labels"].items()))): r["value"]
            for r in mon.registry.snapshot()
            if r["name"].startswith("monitor.kernels.")}
    finally:
        monitor.disable()
    print(json.dumps({"kernel_calls": calls}), flush=True)
    out = {"seed": seed, "platform": jax.devices()[0].platform,
           "chips": chips, "pairs_a_chip_and_layer": pairs,
           "capacity_a_destination": cap, "uniform": pairs // chips,
           "kernel_calls": calls, "readings": []}
    print(json.dumps({k: out[k] for k in (
        "chips", "pairs_a_chip_and_layer", "capacity_a_destination",
        "uniform")}), flush=True)
    done, per = 0, len(made)
    while True:
        params = trainer.state["params"]
        got = [jax.device_get(read(params, b["ids"])) for b in made]
        reading = {k: [[float(v) for v in np.asarray(g[k])] for g in got]
                   for k in READ}
        fullest = max(max(r) for r in reading["exchange_fullest"])
        out["readings"].append(dict(
            reading, step=done, fullest_over_capacity=fullest / cap,
            tier_max=max(max(r) for r in reading["exchange_tier"]),
            bias_abs_max=float(np.max(np.abs(np.asarray(
                params["router_bias"]))))))
        print(json.dumps(out["readings"][-1]), flush=True)
        if done >= steps:
            break
        for _ in range(10 // per):
            losses = np.asarray(trainer.run_steps(staged, float(config["lr"])))
            done += per
        print(json.dumps({"step": done, "loss": float(losses[-1])}),
              flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kanana2_routing_watch_%d.json" % seed),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
