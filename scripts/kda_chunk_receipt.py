"""The delta rule's kernels (``kernels/kda_chunk.py:kda_chunk``: Pallas,
forward and backward) on the chip with the model's operands (q, k, v in
bf16, log-decays and write strengths float32), against the same chunks in
``jnp`` (``kda_chunked``) and against the recurrence a token at a time in
float32 at ``highest`` precision (``kda_recurrence``), at the Kimi-Linear
cell's shapes, or with ``solar`` at ``solar_open2_250b.s4096_scan``'s ([1,
4096, 64 x 128], write strengths ``2 sigmoid(.)`` in (0, 2), q, k and v
behind a ``silu`` as the mixer's filters leave them, the solve by doubling:
``over_one``; beside it what the squarings' solve reads there and with every
write at 1.999):

    chiprun --timeout 1500 -- python3 scripts/kda_chunk_receipt.py [out.json] [kimi|solar]

- the output at [1, 16384, 32, 128], chunks of 64, with decays near one (a
  state that outlives every chunk) and at the seeded extremes (``a_log`` =
  ln 16, a step of 0.7: the overflow case, which must come out finite);
- the five gradients (q, k, v, g, beta) at 1,024 tokens (the recurrence's
  gradient keeps a [32, 128, 128] state a token: 2.1 GB), both decays;
- a control with the state dropped at every chunk edge (each chunk run from
  a zero state), which must NOT pass at decays near one;
- the milliseconds a call at the whole shape, kernels and ``jnp`` form (host
  clock around ``block_until_ready``, the median of CALLS): the forward
  that keeps nothing (``forward``), the forward that runs for a backward
  (``saving_forward``: ``jax.vjp``'s, which writes what the backward reads),
  the backward ALONE (``backward``: the ``vjp`` function of those kept
  values) and both in one program (``forward_and_backward``), beside the
  least the requirement's bytes allow
  (``benchmark/flops/kimi_linear_train.py:delta_rule``); ``kept_bytes``:
  what the saving forward handed the backward less the operands' own bytes,
  by the arrays themselves; ``first_forward_sha256``: of the kernels' output
  at the whole shape, decays near one (two trees that read the same made the
  same ``o``).

One JSON line, kept under ``chiprun_out/pr72/`` (PR 59's and PR 67's under
their own).  The script reads the tree it lies in: copied into
``_checkout/parent/scripts/`` it reads the parent's kernels, so one chip call
holds parent beside change.  Exit 1 where a reading is off, 2 off a TPU."""

import hashlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.flops import kimi_linear_train  # noqa: E402
from benchmark.harness.peaks import PEAKS  # noqa: E402
from paddle_tpu.kernels import kda_chunk as K  # noqa: E402

B, S, H, D, CHUNK = 1, 16384, 32, 128, 64
OVER_ONE = False        # ``solar``: strengths in (0, 2), operands past a silu
S_COMPARED = 1024       # the recurrence's gradient keeps a state a token
DECAYS = {"near_one": (0.0, 1e-3), "extreme": (math.log(16.0), 0.7)}
NAMES = ("q", "k", "v", "g", "beta")
# bf16 operands of a chain of products against float32; the log-decays'
# gradient is a sum of both signs over every later token of the chunk
LIMIT, DECAY_LIMIT = 2e-2, 6e-2
CALLS = 9
OUT = os.path.join(ROOT, "chiprun_out", "pr72", "kda_chunk_receipt.json")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def operands(s, decays, seed=0, strength=None):
    """As the mixer hands them over: q and k L2-normalised a head (q times
    d^-1/2) in bf16, v in bf16, g <= 0 and beta in (0, 1) float32; OVER_ONE:
    q, k, v behind a ``silu`` and beta in (0, 2) (``strength``: every write
    that)."""
    a_log, step = DECAYS[decays]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (B, s, H, D)) for key in ks[:3])
    if OVER_ONE:
        q, k, v = (jax.nn.silu(a) for a in (q, k, v))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -math.exp(a_log) * jax.nn.softplus(
        0.3 * jax.random.normal(ks[3], (B, s, H, D))
        + math.log(math.expm1(step)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, s, H)))
    if OVER_ONE:
        beta = 2.0 * beta if strength is None else jnp.full_like(beta,
                                                                 strength)
    return tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (g, beta)


def edges_dropped(q, k, v, g, beta):
    """The control: every chunk from a zero state."""
    cut = lambda a: a.reshape((-1, CHUNK) + a.shape[2:])    # noqa: E731
    o = K.kda_chunked(*(cut(a) for a in (q, k, v, g, beta)), chunk=CHUNK,
                      over_one=OVER_ONE)
    return o.reshape(v.shape)


def kernels(q, k, v, g, beta, over_one=None):
    """``kda_chunk`` on operands shaped as ``kda_chunked``'s: a head a lane
    block of [b, S, heads x 128] at the kernels' door."""
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))         # noqa: E731
    return K.kda_chunk(
        flat(q), flat(k), flat(v), flat(g), beta, heads=H, chunk=CHUNK,
        over_one=OVER_ONE if over_one is None else over_one).reshape(v.shape)


def _ms(fn, args):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(took))


def solves_compared(recurrence):
    """``solar``: the kernels' output by either solve, float32 OPERANDS (so
    that the solve's own digits show), strengths in (0, 2) and every write at
    1.999, decays near one: each one's relative error against the
    recurrence."""
    out = {}
    for name, strength in (("under_two", None), ("all_1.999", 1.999)):
        args = operands(S_COMPARED, "near_one", seed=3, strength=strength)
        args = tuple(a.astype(jnp.float32) for a in args)
        want = recurrence(*args)
        out[name] = {
            solve: _rel(jax.jit(lambda *a, o=over_one: kernels(
                *a, over_one=o))(*args), want)
            for solve, over_one in (("doubling", True), ("squarings", False))}
    return out


def main(out_path=OUT, shape="kimi"):
    global S, H, OVER_ONE
    if shape == "solar":
        S, H, OVER_ONE = 4096, 64, True
    if jax.devices()[0].platform != "tpu":
        print("kda_chunk_receipt: no TPU here")
        return 2
    assert K.supported((B, S, H, D), D, CHUNK, jnp.bfloat16)
    forms = {"kernel": kernels,
             "jnp": lambda *a: K.kda_chunked(*a, chunk=CHUNK,
                                             over_one=OVER_ONE)}
    recurrence = jax.jit(K.kda_recurrence)
    out = {"shape": [B, S, H, D], "chunk": CHUNK, "limit": LIMIT,
           "over_one": OVER_ONE,
           "decay_limit": DECAY_LIMIT, "device": jax.devices()[0].device_kind,
           "outputs": {}, "gradients": {}, "control": {}, "ms": {}}
    ok = True
    for decays in DECAYS:
        args = operands(S, decays)
        want = recurrence(*args)
        got = jax.jit(forms["kernel"])(*args)
        finite = bool(jnp.isfinite(got.astype(jnp.float32)).all())
        out["outputs"][decays] = {
            "relative_error": _rel(got, want), "finite": finite,
            "against_jnp": _rel(got, jax.jit(forms["jnp"])(*args)),
            "jnp_relative_error": _rel(jax.jit(forms["jnp"])(*args), want)}
        ok &= finite and all(out["outputs"][decays][n] < LIMIT for n in (
            "relative_error", "against_jnp"))
        short = operands(S_COMPARED, decays, seed=1)
        w = jax.random.normal(jax.random.PRNGKey(7), (B, S_COMPARED, H, D))

        def grads(fn):
            return jax.jit(jax.grad(lambda *a: jnp.sum(
                fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2, 3, 4)))(
                    *short)

        got_g, jnp_g, want_g = (grads(fn) for fn in (
            forms["kernel"], forms["jnp"], K.kda_recurrence))
        out["gradients"][decays] = {
            n: {"relative_error": _rel(a, c), "against_jnp": _rel(a, b),
                "jnp_relative_error": _rel(b, c)}
            for n, a, b, c in zip(NAMES, got_g, jnp_g, want_g)}
        ok &= all(e[key] < (DECAY_LIMIT if n == "g" else LIMIT)
                  for n, e in out["gradients"][decays].items()
                  for key in ("relative_error", "against_jnp"))
        out["control"][decays] = _rel(jax.jit(edges_dropped)(*args), want)
    ok &= out["control"]["near_one"] > 10 * LIMIT
    # the time of a call at the whole shape, seeded decays' mix
    args = operands(S, "near_one", seed=2)
    w = jax.random.normal(jax.random.PRNGKey(8), (B, S, H, D), jnp.bfloat16)
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))         # noqa: E731
    # each form on the arrays as it takes them at the mixer's door: the
    # kernels a head a lane block (re-laying [b, S, H, d] out is a copy)
    timed = {"kernel": (lambda *a: K.kda_chunk(*a, heads=H, chunk=CHUNK,
                                               over_one=OVER_ONE),
                        tuple(flat(a) for a in args[:4]) + args[4:], flat(w)),
             "jnp": (forms["jnp"], args, w)}
    for form, (fn, operands_, w_) in timed.items():
        both = jax.jit(jax.grad(lambda *a, fn=fn, w_=w_: jnp.sum(
            (fn(*a) * w_).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
        # the backward alone: ``jax.vjp``'s function is a pytree of what
        # the saving forward kept
        saving = jax.jit(lambda *a, fn=fn: jax.vjp(fn, *a))
        o, kept = jax.block_until_ready(saving(*operands_))
        out["ms"][form] = {
            "forward": _ms(jax.jit(fn), operands_),
            "saving_forward": _ms(saving, operands_),
            "backward": _ms(jax.jit(lambda f, c: f(c)), (kept, w_.astype(
                o.dtype))),
            "forward_and_backward": _ms(both, operands_),
            "kept_bytes": sum(a.nbytes for a in jax.tree_util.tree_leaves(
                kept)) - sum(a.nbytes for a in operands_)}
        if form == "kernel":
            out["first_forward_sha256"] = hashlib.sha256(np.asarray(
                jax.jit(fn)(*operands_).astype(jnp.float32)).tobytes()
            ).hexdigest()
        del o, kept
    model = {"linear_attn_config": {"num_heads": H, "head_dim": D}}
    need = kimi_linear_train.delta_rule(model, B * S)
    peaks = PEAKS["TPU v5 lite"]
    out["least_ms_a_layer_and_step"] = 1e3 * max(
        need["flops"] / peaks["bf16_flops"],
        need["bytes"] / peaks["hbm_bytes_per_s"])
    out["kept_state_bytes"] = K.kept_state_bytes(B, S, CHUNK, H, D, D)
    if OVER_ONE:
        out["solves"] = solves_compared(recurrence)
        out["ms"]["kernel_squarings"] = {"forward_and_backward": _ms(
            jax.jit(jax.grad(lambda *a: jnp.sum((K.kda_chunk(
                *a, heads=H, chunk=CHUNK) * flat(w)).astype(jnp.float32)),
                argnums=(0, 1, 2, 3, 4))), timed["kernel"][1])}
    out["ok"] = bool(ok)
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
