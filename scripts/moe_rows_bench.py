"""The held experts' sum back, measured on the chip: the Pallas row kernel
(``kernels.moe_rows.moe_rows_sum``) against XLA's gather-and-sum, at one
layer's shapes of the three cells that hold a share of their experts and at
OLMoE's, which holds them all (bf16):

    chiprun -- python3 scripts/moe_rows_bench.py [out.json]

================  =========  =  =====  =======  ==================
cell              slots T*k  k  E      rows M   slots with a row
================  =========  =  =====  =======  ==================
smallthinker      98,304     6  2,560  30,720   24,576 (16 of 64)
lfm2              65,536     4  2,048  20,480   16,384 (8 of 32)
mistral_small_4   65,536     4  4,096  5,120    4,096 (8 of 128)
olmoe (all held)  131,072    8  2,048  131,072  131,072
================  =========  =  =====  =======  ==================

A shape's places are drawn as balanced routing gives them: the held pairs
at random slots, their rows a random permutation.  Milliseconds by the host
clock around ``block_until_ready`` over REPS calls after a warm-up:

- ``xla``: what ``moe._combine`` was before the kernel,
  ``sum(rows.at[inv].get(fill=0).astype(f32), 1)`` (OLMoE: ``rows[inv]``);
- ``kernel``: ``moe_rows_sum`` whole, the pass that makes 32-bit words of
  the bf16 rows included; ``words``: that pass alone;
- ``kernel.no_row``: the kernel on places that hold no row at all, so
  ``(kernel.no_row - words) / slots`` is what a slot WITHOUT a row costs (a
  key in the blocks' sorts; the zeroing, summing and writing of the blocks
  ride on it), and ``(kernel - kernel.no_row) / rows fetched`` what a
  FETCHED row adds.

- ``dispatch.xla`` / ``dispatch.kernel``: the dispatch's M rows
  ``x[order // k]`` by XLA's gather and by the same kernel at k = 1, which
  the layer does NOT take: 0.24 ms a call in SmallThinker's step by XLA.

``equal`` says whether the kernel's output is the float32 sum in slot order
bit for bit (``in_slot_order``), on the timed places and on routing that
leaves some tokens every row and others none (``equal_clumped``);
``equal_to_xla_s_reduce`` whether it is also ``gather_and_sum``'s, whose
reduce XLA orders as it likes; ``digest`` is the sha1 of the kernel's
bytes.  Exit 1 where ``equal`` fails; off a TPU it exits 2 (a CPU time is
not a device time)."""

import functools
import hashlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (pair slots T*k, k, E, rows M, slots that hold a row)
SHAPES = {"smallthinker": (98304, 6, 2560, 30720, 24576),
          "lfm2": (65536, 4, 2048, 20480, 16384),
          "mistral_small_4": (65536, 4, 4096, 5120, 4096),
          "olmoe_all_held": (131072, 8, 2048, 131072, 131072)}
REPS = 10


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS * 1e3


def places(rng, slots, m, held, k, clumped=False):
    """``inv`` [slots] int32: ``held`` slots get the rows [0, held) in a
    random order and the others ``m``.  ``clumped``: the held slots are
    whole tokens from the front, so some tokens sum k rows and most none."""
    inv = np.full(slots, m, np.int32)
    at = (np.arange(held) if clumped
          else rng.choice(slots, size=held, replace=False))
    inv[at] = rng.permutation(held)
    return jnp.asarray(inv)


@functools.partial(jax.jit, static_argnums=(2, 3))
def gather_and_sum(rows, inv, k, absent):
    """``moe._combine`` as it was before the kernel."""
    if absent:
        back = rows.at[inv.reshape(-1, k)].get(mode="fill", fill_value=0)
    else:
        back = rows[inv].reshape((-1, k) + rows.shape[1:])
    return jnp.sum(back.astype(jnp.float32), axis=1).astype(rows.dtype)


gather = jax.jit(lambda x, token: x[token])


@functools.partial(jax.jit, static_argnums=(2,))
def in_slot_order(rows, inv, k):
    """The float32 sum of a token's rows, slot 0 first, one add a slot: the
    order the kernel keeps.  XLA's reduce over the k of ``gather_and_sum``
    keeps it where most slots are the zero row, and not at OLMoE's eight
    full slots (the same numbers, last bits apart)."""
    total = jnp.zeros((inv.shape[0] // k, rows.shape[1]), jnp.float32)
    for j in range(k):
        total = total + rows.at[inv[j::k]].get(
            mode="fill", fill_value=0).astype(jnp.float32)
    return total.astype(rows.dtype)


def _same(a, b):
    return bool(jnp.all(jax.lax.bitcast_convert_type(a, jnp.uint16)
                        == jax.lax.bitcast_convert_type(b, jnp.uint16)))


def main(out_path=None):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("moe_rows_bench: needs a TPU, found %s" % dev.platform,
              file=sys.stderr)
        return 2
    from paddle_tpu.kernels import moe_rows

    got = {"device_kind": dev.device_kind, "reps": REPS, "shapes": {}}
    kernel = jax.jit(moe_rows.moe_rows_sum, static_argnums=(2,))
    words = jax.jit(lambda rows: moe_rows._words(rows, False))
    ok = True
    for name, (slots, k, width, m, held) in SHAPES.items():
        rng = np.random.RandomState(slots + k)
        rows = jax.random.normal(jax.random.PRNGKey(k), (m, width),
                                 jnp.float32).astype(jnp.bfloat16)
        inv = places(rng, slots, m, held, k)
        absent = held < slots
        out = kernel(rows, inv, k)
        equal = _same(out, in_slot_order(rows, inv, k))
        equal_xla = _same(out, gather_and_sum(rows, inv, k, absent))
        clumped = places(rng, slots, m, held, k, clumped=True)
        equal_clumped = _same(kernel(rows, clumped, k),
                              in_slot_order(rows, clumped, k))
        # the dispatch's rows, token order[i] // k for row i: XLA's gather
        # against the same kernel at k = 1 (every slot holds a row)
        order = np.asarray(rng.randint(0, slots, m), np.int32)
        at = np.flatnonzero(np.asarray(inv) < m)
        order[np.asarray(inv)[at]] = at
        token = jnp.asarray(order // k)
        x = jax.random.normal(jax.random.PRNGKey(m), (slots // k, width),
                              jnp.float32).astype(jnp.bfloat16)
        dispatched = kernel(x, token, 1)
        equal_dispatch = _same(dispatched, gather(x, token))
        ms = {"xla": _time(gather_and_sum, rows, inv, k, absent),
              "dispatch.xla": _time(gather, x, token),
              "dispatch.kernel": _time(kernel, x, token, 1),
              "kernel": _time(kernel, rows, inv, k),
              "kernel.no_row": _time(kernel, rows, jnp.full_like(inv, m), k),
              "words": _time(words, rows)}
        one = {"slots": slots, "k": k, "width": width, "rows": m,
               "rows_fetched": held, "ms": ms,
               "token_block": moe_rows.token_block(k, width // 2),
               "ns_a_skipped_slot": (ms["kernel.no_row"] - ms["words"])
               / slots * 1e6,
               "ns_a_fetched_row": (ms["kernel"] - ms["kernel.no_row"])
               / held * 1e6,
               "xla_ns_a_slot": ms["xla"] / slots * 1e6,
               "equal": equal, "equal_clumped": equal_clumped,
               "equal_to_xla_s_reduce": equal_xla,
               "equal_dispatch": equal_dispatch,
               "digest": hashlib.sha1(np.asarray(
                   jax.lax.bitcast_convert_type(out, jnp.uint16)
               ).tobytes()).hexdigest()}
        ok = ok and equal and equal_clumped and equal_dispatch
        got["shapes"][name] = one
        print(name, json.dumps(one), flush=True)
    print(json.dumps(got), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(got, f)
    if not ok:
        print("moe_rows_bench: the kernel's sum differs from the "
              "gather-and-sum's", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
