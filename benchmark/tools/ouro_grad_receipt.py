"""A chip-side receipt for the BACKWARD of ``ouro_2_6b``'s shared leaves,
which ``correct`` does not see (PERF.md section 7 (bh)): one trained step's
gradient of every leaf at the PUBLISHED WIDTHS, the layers' leaves summed
over the four passes in the carry of the passes' scan (in bf16, one rounding
a pass more than a plain stack has), against ``jax.grad`` of the plain
float32 reference on the same weights and batch.

    python3 benchmark/tools/ouro_grad_receipt.py [seed] [out.json] [layers] [B] [S]

The cut is deeper than the cell's (2 layers, B = 1, S = 1,024 where the cell
has 12, 2 and 4,096): the reference differentiates through its Python loop
with no remat, and 8 layer applications and four exits' logits of one
sequence in float32 are what fits one chip (two sequences do not: the
allocator's refusal, my chip run, PR 54).  Widths, passes, vocabulary,
seeding, optimizer and the program's path (``build_ouro_trainer``,
``run_steps``) are the cell's.

The program's gradient is read off its own state: AdamW's first moment
after ONE step from zero is ``(1 - beta1) g`` in the leaf's type.  A leaf's
reading is ``|g_program - g_reference| / |g_reference|`` over the whole leaf.
Beside it stands what a DROPPED PASS would read: the reference's gradient at
three passes against its own at four (the loss of a three-pass model is
another loss, so this says how large one pass's part is, not what one
particular fault gives).  The readings are the chip's alone."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402

NAME, CELL = "ouro_2_6b", "ouro_2_6b.s4096_scan"


def _reference_grads(ref, params, ids, model):
    import jax
    import jax.numpy as jnp

    loss, grads = jax.value_and_grad(
        lambda p: ref.forward(p, ids, model)[0])(
            jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params))
    return float(loss), jax.tree.map(np.asarray, grads)


def _distance(got, want):
    got, want = (np.asarray(a, np.float32).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def main(seed=0, out_path=None, layers=2, batch=1, seq=1024):
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.train import stack_batches

    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    ref = mf.module("reference", config["reference"])
    model = dict(config["model"], num_hidden_layers=int(layers))
    cfg = build._call(config["config_factory"], n_layers=int(layers))
    trainer = build._call(
        config["trainer_builder"], cfg,
        build.resolve(config["mesh_spec"])(**traffic["mesh"]),
        optimizer=build._call(config["optimizer"]), seed=int(seed),
        devices=jax.devices()[:1])
    beta1 = 0.9         # ``optim.adamw``'s default, the configuration's
    assert not config["optimizer"]["kwargs"], config["optimizer"]
    params = jax.tree.map(np.asarray, trainer.state["params"])
    b = batches.host_batch(config["batch_fields"],
                           {"B": int(batch), "S": int(seq)}, int(seed), 0)
    staged = stack_batches(trainer.mesh, {"ids": P(config["batch_axis"])},
                           [b])
    first = float(np.asarray(trainer.run_steps(staged, float(config["lr"])),
                             np.float32)[0])
    got = jax.tree.map(lambda m: np.asarray(m, np.float32) / (1.0 - beta1),
                       trainer.state["opt"]["m"])
    del trainer, staged
    want_loss, want = _reference_grads(ref, params, b["ids"], model)
    _, fewer = _reference_grads(
        ref, params, b["ids"],
        dict(model, total_ut_steps=int(model["total_ut_steps"]) - 1))
    out = {"config": config["name"], "seed": int(seed),
           "platform": jax.devices()[0].platform, "layers": int(layers),
           "batch": int(batch), "seq": int(seq),
           "passes": int(model["total_ut_steps"]), "program_loss": first,
           "reference_loss": want_loss,
           "loss_relative_error": abs(first - want_loss) / want_loss,
           "leaves": {}}
    flat = jax.tree_util.tree_leaves_with_path(want)
    for path, w in flat:
        name = "/".join(k.key for k in path)
        g, f = (np.asarray(t) for t in (_leaf(got, path), _leaf(fewer, path)))
        out["leaves"][name] = {
            "dtype": str(_leaf(params, path).dtype),
            "reference_norm": float(np.linalg.norm(w)),
            "program_error": _distance(g, w),
            "one_pass_fewer": _distance(f, w)}
        print(name, json.dumps(out["leaves"][name]), flush=True)
    print(json.dumps({k: v for k, v in out.items() if k != "leaves"}),
          flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)
    return out


def _leaf(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree


if __name__ == "__main__":
    main(*sys.argv[1:])
