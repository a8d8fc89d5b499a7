"""The LM head alone at the cells' shapes, timed and held to float32:

    chiprun -- python3 scripts/lm_head_carry_bench.py [out.json] \
        [--tree DIR] [N,E,V[,live] ...]

For each (rows a device, width, vocabulary[, share of the rows live]) the
value and the gradients of x, the norm's scale and the head matrix of the
tp=1 head on bf16 operands as the cells hold them, one jitted program:
milliseconds a call by the host's clock around ``CALLS`` calls (the device
is busy throughout: a call is tens of milliseconds), the compiler's
temporaries, and how far the loss, dx, dscale and demb lie from the plain
dense formula on the SAME operands in float32 (``precision=HIGHEST``, a row
block at a time), the largest and the root-mean-square distance over the
reference's largest entry.  ``--tree DIR`` takes ``paddle_tpu`` from another
checkout (``git archive`` of a commit under ``_checkout/``): a tree from
before PR 74 has the per-row head whose backward makes the logits a second
time (``_chunked_vocab_nll``), and two runs of one shape, one a tree, say
which head's gradients lie nearer the float32 ones.  The default shapes are
PR 74's table (OLMoE, SmallThinker, kanana a chip, Jamba, Ouro's four
exits, BERT's 15 % of 32,768 rows).  Exit 2 off a TPU."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPES = ["16384,2048,50304", "16384,2560,37984", "8192,2048,128256",
          "8192,2560,65536", "32768,2048,49152", "32768,768,30528,0.15"]
CALLS = 5
NORM = ("rms", 1e-5)


def float32_reference(jax, jnp, x, scale, emb, labels, wgt, block=1024):
    """The loss and its gradients by the dense formula, every operand and
    matmul in float32, a block of rows at a time."""
    highest = jax.lax.Precision.HIGHEST

    def loss(x, scale, emb, labels, wgt):
        x = x.astype(jnp.float32)
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + NORM[1]) \
            * scale
        logits = jnp.matmul(h, emb.T, precision=highest)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[:, None], -1)[:, 0]
        return jnp.sum(wgt * nll)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    emb = emb.astype(jnp.float32)
    total, dx, dscale, demb = 0.0, [], 0.0, 0.0
    for lo in range(0, x.shape[0], block):
        rows = slice(lo, lo + block)
        part, (dxb, ds, de) = step(x[rows], scale, emb, labels[rows],
                                   wgt[rows])
        total, dscale, demb = total + part, dscale + ds, demb + de
        dx.append(dxb)
    return total, jnp.concatenate(dx), dscale, demb


def main(argv):
    tree = ROOT
    if "--tree" in argv:
        at = argv.index("--tree")
        tree = os.path.abspath(argv[at + 1])
        del argv[at:at + 2]
    sys.path.insert(0, tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.parallel import transformer as T

    if jax.devices()[0].platform != "tpu":
        print("lm_head_carry_bench: no TPU, nothing to measure",
              file=sys.stderr)
        return 2
    out = argv.pop(0) if argv and argv[0].endswith(".json") else None
    carried = hasattr(T, "_weighted_vocab_nll")

    def head(x, scale, emb, labels, wgt):
        if carried:
            return T._weighted_vocab_nll(x, scale, None, emb, labels, wgt,
                                         norm=NORM)[0]
        return jnp.sum(T._chunked_vocab_nll(x, scale, None, emb, labels, wgt,
                                            norm=NORM) * wgt)

    results = []
    for shape in argv or SHAPES:
        n, e, v, *live = shape.split(",")
        n, e, v, live = int(n), int(e), int(v), float(live[0]) if live else 1.0
        rng = np.random.RandomState(n + e + v)
        mask = (rng.rand(n) < live).astype(np.float32)
        args = (jnp.asarray(rng.randn(n, e), jnp.bfloat16),
                jnp.asarray(1 + 0.1 * rng.randn(e), jnp.float32),
                jnp.asarray(0.02 * rng.randn(v, e), jnp.bfloat16),
                jnp.asarray(rng.randint(0, v, n), jnp.int32),
                jnp.asarray(mask / mask.sum()))
        row = {"tree": os.path.relpath(tree, ROOT), "rows": n, "width": e,
               "vocabulary": v, "live": live,
               "head": "carries" if carried else "remakes",
               "row_block": T.head_row_block(n)}
        compiled = jax.jit(jax.value_and_grad(
            head, argnums=(0, 1, 2))).lower(*args).compile()
        jax.block_until_ready(compiled(*args))
        start = time.perf_counter()
        for _ in range(CALLS):
            got = compiled(*args)
        jax.block_until_ready(got)
        row["ms"] = (time.perf_counter() - start) / CALLS * 1e3
        row["temp_mb"] = compiled.memory_analysis().temp_size_in_bytes / 1e6
        want = float32_reference(jax, jnp, *args)
        # the loss, dx, dscale, demb against float32's
        for key, fn in (("max_from_float32", np.max),
                        ("rms_from_float32",
                         lambda a: np.sqrt(np.mean(np.square(a))))):
            row[key] = [
                float(fn(np.abs(np.asarray(a, np.float32) - np.asarray(b)))
                      / max(np.max(np.abs(np.asarray(b))), 1e-30))
                for a, b in zip(jax.tree.leaves(got), want)]
        results.append(row)
        print(json.dumps(row), flush=True)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
