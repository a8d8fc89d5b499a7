"""The LFM2 cell's program in FLOAT32 against its reference, on the chip, at
the published widths and the timed sizes: what separates a fault of the
compiled path (the Mosaic-compiled grouped flash kernels at two heads a lane
block, the grouped matmuls, the prefix layer, the period scan) from bf16
rounding.  The benchmark's witness reads the bf16 program, whose near-tied
experts flip under rounding (PERF.md section 6, PR 33); this reads the same
forward with float32 weights and activations under
``jax.default_matmul_precision("highest")``, where nothing flips, and prints
the quartiles of each witnessed position's relative error.

    chiprun -- python3 scripts/lfm2_float32_receipt.py [seed] [out.json]

Exits 1 where the third quartile is over 1e-3, 2 off a TPU."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402

NAME, CELL = "lfm2_8b_a1b", "lfm2_8b_a1b.s8192_scan"
LIMIT = 1e-3


def main(seed=0, out_path=None):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import decoder, transformer as T

    # float32 rows and weights are twice the bytes: the grouped matmuls'
    # tiles follow the element size (``moe._tiling`` keeps a grid step's
    # blocks within its VMEM budget), so this process sets none of its own

    if jax.devices()[0].platform != "tpu":
        print("no TPU here", file=sys.stderr)
        return 2
    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    cfg = build._call(config["config_factory"], dtype="float32")
    params = T.init_transformer_params(jax.random.PRNGKey(int(seed)), cfg)
    ids = batches.host_batch(config["batch_fields"],
                             build.cell_dims(config, traffic), int(seed),
                             0)["ids"]
    ref = mf.module("reference", config["reference"])
    at = ref.witness_positions(ids.shape[1])

    @jax.jit
    def logits(params, ids):
        x, _ = decoder.forward(params, ids, cfg)
        return T.head_logits(params, x[:, at], cfg)

    with jax.default_matmul_precision("highest"):
        got = np.asarray(logits(params, jnp.asarray(ids)))
    host = jax.tree.map(np.asarray, params)
    del params
    each = ref.position_errors(got, host, {"ids": ids}, config["model"])
    out = {"seed": int(seed), "positions": len(each),
           "quartiles": [float(q) for q in np.quantile(
               each, (0.0, 0.25, 0.5, 0.75, 1.0))],
           "limit": LIMIT}
    print(json.dumps(out), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(dict(out, each=[float(e) for e in each]), f)
    return int(out["quartiles"][3] > LIMIT)


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
