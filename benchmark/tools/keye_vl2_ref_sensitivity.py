"""How far the faults that ``correct`` is there to catch move the
``keye_vl2_30b_a3b`` reference at the published widths and the timed sizes,
by both of the cell's limits: its loss (``TOLERANCE``) and its logits at the
witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to; each group's third
quartile printed beside it: the positions before 2,048, where nothing is
dropped, from it on, spread, and the last).  The reference with a fault put
in (``reference.FAULTS``: no selection (dense causal), the top 1,024 for
2,048, the indexer's weights ``w`` dropped, no ReLU, un-rotated indexer
keys, the selection of the previous row, key/value head ``n // 4``, 7 of 8
experts, the spatial sections swapped (inert at text positions, where the
three streams are equal: the CPU tests drive them apart), and the three
precisions: bfloat16 throughout, bfloat16 in the layers alone, those layers
on float8 weights), on the weights the program seeds and the cell's first batch.

    python3 benchmark/tools/keye_vl2_ref_sensitivity.py [seed] [out.json] [fault ...]
    python3 benchmark/tools/keye_vl2_ref_sensitivity.py agreement [seed]

Faults named after the two are the only ones thrown; ``none`` throws none
and reads the sound program alone.  It is ``jamba_ref_sensitivity.py``'s
procedure (one definition of what is read and printed) on this
configuration and cell; the readings are the chip's alone.

``agreement``: selection is discontinuous, so the program (bf16 operands on
the chip) and the reference (float32) may select different keys within
rounding of a row's threshold.  Prints, for the FIRST layer at the witness's
rows past 2,048, the share of each row's selected keys that both select
(intersection over union; mean, least) and the mean number of keys a row
that only one of them selects."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from benchmark.tools import jamba_ref_sensitivity as procedure  # noqa: E402

NAME, CELL = "keye_vl2_30b_a3b", "keye_vl2_30b_a3b.s16384_scan"


def agreement(seed=0):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import decoder, transformer

    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    trainer = build.build_trainer(config, traffic, int(seed),
                                  jax.devices()[:1])
    cfg, params = trainer.cfg, trainer.state["params"]
    ids = batches.host_batch(config["batch_fields"],
                             build.cell_dims(config, traffic), int(seed),
                             0)["ids"]
    ref = mf.module("reference", config["reference"])
    s = ids.shape[1]
    rows = ref.witness_positions(s)
    rows = rows[rows >= cfg.indexer_topk]

    def program(p, ids):
        pl, h = decoder._first_layer_input(p, ids, cfg)
        scores, tau = transformer.indexer_selection(pl, h, cfg)
        return (scores[0, rows] >= tau[0, rows, None]) \
            & (jnp.arange(s)[None] <= rows[:, None])

    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))
    host = jax.tree.map(np.asarray, params)
    layer = {name: jnp.asarray(host["params_layers"][name][0], jnp.float32)
             for name in ref.ATTENTION_LEAVES}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention_part(
            jnp.asarray(host["tok_emb"][ids[0]], jnp.float32), layer,
            jnp.broadcast_to(jnp.arange(s), (3, s)), config["model"])[2])[rows]
    both, either = (got & want).sum(-1), (got | want).sum(-1)
    out = {"seed": int(seed), "platform": jax.devices()[0].platform,
           "rows": int(len(rows)), "agreement_mean": float(np.mean(
               both / either)), "agreement_least": float(np.min(
                   both / either)),
           "keys_one_side_only_mean": float(np.mean(either - both)),
           "keys_selected_mean": float(np.mean(want.sum(-1)))}
    print("agreement", json.dumps(out), flush=True)
    return out


def main(*argv):
    if argv and argv[0] == "agreement":
        return agreement(*argv[1:])
    procedure.NAME, procedure.CELL = NAME, CELL
    return procedure.main(*argv)


if __name__ == "__main__":
    main(*sys.argv[1:])
