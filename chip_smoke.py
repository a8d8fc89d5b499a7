"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives the main paths once, through the entry points a user
calls, on the TPU JAX finds — and fails if JAX finds none (there is no CPU
mode; tests/test_chip_smoke.py rehearses the phase functions at tiny sizes):

- kernels: the Mosaic flash-attention and layer-norm kernels against the
           repo's XLA references on a small input, values and gradients;
- train:   BERT-base at full width exactly as ``bench.bench_bert`` builds it
           (hidden 768, 12 layers, 12 heads, FFN 3072, vocab 30528, bf16,
           B=64, S=512, scan_unroll=12, LAMB) through ``trainer.run_steps``;
           the compiled step must contain the Mosaic flash-attention
           (forward + fused backward) and layer-norm kernels;
- program: a Fluid ``Program`` (DeepFM's dense head at its default widths)
           trained by ``Executor(TPUPlace())``, saved and exported;
- serve:   ``ServeEngine`` over that artifact, mixed-size requests, zero
           recompiles, results equal to a direct predictor run;
- four_chip (when the host has >= 4 devices): pp=2 x tp=2 and dp=4 ZeRO
           steps at BERT-base width, the MoE all-to-all and the
           program-path dp x tp step (``__graft_entry__.dryrun_multichip``).

Every failure is fatal: nothing on this path turns a failed phase into a
printed line.  Phase wall times are set-up information (compile included),
never a rate or a latency of the system.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# beside the checkout, in the one directory the chip tool copies back
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

FLASH_AND_LN_KERNELS = ("flash_fwd", "flash_bwd_fused",
                        "layer_norm_fwd", "layer_norm_bwd")


def say(msg):
    print("chip_smoke: " + msg, flush=True)


# ---------------------------------------------------------------- kernels --

def kernels_phase(batch=2, seq=512, heads=12, head_dim=64):
    """The two kernels of the train step against the repo's XLA references
    on a small input, values and gradients: packed flash attention (forward
    + fused backward, the 512-block path BERT takes) vs
    ``ring_attention(axis=None)``, fused layer norm vs
    ``transformer.layer_norm(fused=False)``.  Inputs are in the model's
    dtype; the references run in f32 at highest matmul precision.  Returns
    the worst error of each, relative to the reference's largest value."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import flash_attention_packed
    from paddle_tpu.kernels.layer_norm import fused_layer_norm
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.parallel.transformer import layer_norm

    E = heads * head_dim
    rng = np.random.RandomState(3)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)

    q, k, v, w = (arr(batch, seq, E) for _ in range(4))
    scale = jnp.asarray(rng.rand(E) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(E), jnp.float32)

    def flash(q, k, v):
        o = flash_attention_packed(q, k, v, heads, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def flash_ref(q, k, v):
        f32 = [t.astype(jnp.float32).reshape(batch, seq, heads, head_dim)
               for t in (q, k, v)]
        o = ring_attention(*f32, axis=None, causal=False).reshape(q.shape)
        return jnp.sum(o * w.astype(jnp.float32)), o

    def ln(x, s, b):
        y = fused_layer_norm(x, s, b)
        return jnp.sum(y.astype(jnp.float32) * w.astype(jnp.float32)), y

    def ln_ref(x, s, b):
        y = layer_norm(x.astype(jnp.float32), s, b, fused=False)
        return jnp.sum(y * w.astype(jnp.float32)), y

    def worst(fn, ref_fn, args, names):
        (_, y), grads = jax.jit(jax.value_and_grad(
            fn, argnums=tuple(range(len(args))), has_aux=True))(*args)
        with jax.default_matmul_precision("highest"):
            (_, y_ref), grads_ref = jax.jit(jax.value_and_grad(
                ref_fn, argnums=tuple(range(len(args))),
                has_aux=True))(*args)
        errs = {}
        for name, got, want in zip(("out",) + names, (y,) + grads,
                                   (y_ref,) + grads_ref):
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            assert got.shape == want.shape and np.isfinite(got).all(), name
            errs[name] = float(np.abs(got - want).max()
                               / np.abs(want).max())
        return errs

    out = {"flash": worst(flash, flash_ref, (q, k, v), ("dq", "dk", "dv")),
           "layer_norm": worst(ln, ln_ref, (q, scale, bias),
                               ("dx", "dscale", "dbias"))}
    # bf16 carries 8 bits: 2**-8 per rounding, a few roundings deep
    for kern, errs in out.items():
        for name, e in errs.items():
            assert e < 3e-2, (
                "%s %s disagrees with its reference: %.3g of the largest "
                "value" % (kern, name, e), out)
    return {kern: {n: round(e, 5) for n, e in errs.items()}
            for kern, errs in out.items()}


# ------------------------------------------------------------------ train --

def train_phase(devices, cfg, batch, seq):
    """``bench_bert``'s trainer: two ``run_steps`` calls of three steps each
    on one repeated batch.  Asserts finite losses that fall, that the second
    call compiled nothing, and last that the flash-attention and layer-norm
    kernels are Mosaic custom calls of the step — which is where a CPU
    rehearsal, whose kernels interpret, stops."""
    import re

    from paddle_tpu.models import bert
    from paddle_tpu.monitor.recompile import compile_ledger
    from paddle_tpu.parallel import MeshSpec, optim
    from paddle_tpu.parallel.train import stack_batches

    n_steps, lr = 3, 1e-3
    trainer = bert.build_bert_trainer(
        cfg, MeshSpec(1, 1, 1), optimizer=optim.lamb(), devices=devices[:1])
    rng = np.random.RandomState(0)
    one = {
        "ids": rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        "labels": rng.randint(0, cfg.vocab_size,
                              (batch, seq)).astype(np.int32),
        "mask": np.ones((batch, seq), np.float32),
    }
    batches = stack_batches(trainer.mesh, bert.batch_specs(),
                            [one] * n_steps)

    first = np.asarray(trainer.run_steps(batches, lr), np.float32)
    t_second = time.perf_counter()
    second = np.asarray(trainer.run_steps(batches, lr), np.float32)
    # a backend record is a program built, or loaded from the cache
    again = [r["name"] for r in compile_ledger().between(
        t_second, time.perf_counter()) if r["kind"] == "backend"]
    assert not again, "the second run_steps call compiled %s" % again
    losses = np.concatenate([first, second])
    assert losses.shape == (2 * n_steps,), losses.shape
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], (
        "loss did not fall on a repeated batch", losses)

    lowered = trainer.multi_fn.lower(trainer.state, batches, lr)
    found = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    missing = [k for k in FLASH_AND_LN_KERNELS if k not in found]
    assert not missing, (
        "the train step lowered WITHOUT Mosaic kernels %s (found %s): "
        "interpret mode or an XLA fallback branch ran"
        % (missing, sorted(found)))
    # the compiled module (a cache hit: same program as the first call)
    mosaic_calls = lowered.compile().as_text().count("tpu_custom_call")
    assert mosaic_calls > 0, "no tpu_custom_call in the compiled step"
    return {"losses": [round(float(x), 4) for x in losses],
            "kernels": sorted(found), "mosaic_calls": mosaic_calls}


# ---------------------------------------------------------------- program --

def _deepfm_dense_head(fluid, num_fields, embed_dim, mlp_dims):
    """DeepFM's head (models/deepfm._deepfm_head) as a Fluid program over
    the already looked-up rows: emb [B, F*D], lin [B, F] -> logit [B, 1]."""
    L = fluid.layers
    emb = L.data("emb", shape=[num_fields * embed_dim], dtype="float32")
    lin = L.data("lin", shape=[num_fields], dtype="float32")
    label = L.data("label", shape=[1], dtype="float32")
    e3 = L.reshape(emb, [-1, num_fields, embed_dim])
    s = L.reduce_sum(e3, dim=1)                               # [B, D]
    fm = L.scale(L.reduce_sum(
        L.elementwise_sub(L.square(s), L.reduce_sum(L.square(e3), dim=1)),
        dim=1, keep_dim=True), scale=0.5)                     # [B, 1]
    h = emb
    for d in mlp_dims:
        h = L.fc(h, size=d, act="relu")
    deep = L.fc(h, size=1)
    logit = L.elementwise_add(
        L.elementwise_add(L.reduce_sum(lin, dim=1, keep_dim=True), fm), deep)
    loss = L.mean(L.sigmoid_cross_entropy_with_logits(logit, label))
    return logit, loss


def program_phase(out_dir, num_fields, embed_dim, mlp_dims, batch=256):
    """The library surface: build a Program, train it with
    ``Executor(TPUPlace())``, save + export it with a symbolic batch dim.
    Returns the artifact directory (``out_dir``/artifact, a fixed path)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.inference import export_inference_model

    platform = jax.devices()[0].platform      # main() has refused all but tpu
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        logit, loss = _deepfm_dense_head(fluid, num_fields, embed_dim,
                                         mlp_dims)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(1)
    lin = rng.randn(batch, num_fields).astype("f4") * 0.1
    feed = {"emb": rng.randn(batch, num_fields * embed_dim).astype("f4") * 0.1,
            "lin": lin,
            "label": (lin @ rng.randn(num_fields) > 0).astype("f4")
            .reshape(-1, 1)}
    losses = []
    for _ in range(8):                  # one repeated batch: loss must fall
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                        return_numpy=False)
        where = {d.platform for d in lv.devices()}
        assert where == {platform}, (
            "the fetched loss lives on %s, not %s" % (where, platform))
        losses.append(float(np.asarray(lv)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], ("program loss did not fall", losses)

    artifact = os.path.join(out_dir, "artifact")
    shutil.rmtree(artifact, ignore_errors=True)
    os.makedirs(artifact)
    fluid.io.save_inference_model(artifact, ["emb", "lin"], [logit], exe,
                                  main_program=main)
    export_inference_model(
        artifact, feed_shapes={"emb": (4, num_fields * embed_dim),
                               "lin": (4, num_fields)}, poly_batch=True)
    return {"artifact": artifact,
            "losses": [round(x, 4) for x in (losses[0], losses[-1])]}


# ------------------------------------------------------------------ serve --

# One row's answer from executables compiled for different batch shapes.
# The model is f32, but the TPU's default matmul precision rounds f32
# operands to bf16 (8 bits) and each shape gets its own codegen: on the v5e
# a 1-row run and the same row inside a 16-row bucket differed by 1.6e-3 of
# the value (PR 21).  On the CPU the same comparison holds to 1e-5.
_ACROSS_SHAPES = dict(rtol=1e-2, atol=1e-3)


def serve_phase(artifact, num_fields, embed_dim, buckets=(4, 8, 16),
                sizes=(3, 1, 20, 8, 5, 16)):
    """One in-process replica: ``ServeEngine`` over the exported artifact,
    mixed-size requests, steady state never meets XLA, results equal a
    direct predictor run, and a second replica deserializes what the first
    compiled (the warm store refuses nothing)."""
    from paddle_tpu import inference, warm
    from paddle_tpu.inference import load_exported_model
    from paddle_tpu.serving import BucketLattice, ServeEngine

    warm.reset_stats()
    feed_spec = {"emb": ((num_fields * embed_dim,), "float32"),
                 "lin": ((num_fields,), "float32")}
    rng = np.random.RandomState(2)
    reqs = [{"emb": rng.randn(n, num_fields * embed_dim).astype("f4") * 0.1,
             "lin": rng.randn(n, num_fields).astype("f4") * 0.1}
            for n in sizes]
    eng = ServeEngine(load_exported_model(artifact), BucketLattice(buckets),
                      feed_spec=feed_spec, name="chip_smoke")
    with eng:
        futs = [eng.submit(dict(r)) for r in reqs]
        outs = [f.result(timeout=300) for f in futs]
    s = eng.last_summary
    assert eng.error is None, eng.error
    assert s["completed"] == len(sizes), s
    assert s["recompiles"] == 0, s
    assert s["new_compiled_sigs"] == 0, s
    # the reference runs AFTER the summary: it shares the artifact's
    # process-wide compiled call, and its exact-shape compiles would
    # otherwise count as new signatures
    ref = load_exported_model(artifact)
    for r, (got,) in zip(reqs, outs):
        (want,) = ref.run(r)
        assert got.shape == want.shape == (r["emb"].shape[0], 1), got.shape
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, **_ACROSS_SHAPES)
    # a second replica over the same artifact starts with nothing in
    # memory: every lattice point must come off the store beside the
    # artifact (what the first one published), onto the device it was
    # compiled for, and answer the same
    warm.join_background(120)
    inference._EXPORT_MEMO.clear()
    twin = load_exported_model(artifact)
    for b in buckets:
        shapes = {n: ((b,) + row, dt) for n, (row, dt) in feed_spec.items()}
        src, _ = twin.ensure_compiled(shapes)
        assert src == "disk", (
            "lattice point %d was %s, not deserialized" % (b, src))
    twin.declare_batch_buckets(buckets)
    for r, (got,) in zip(reqs, outs):
        if r["emb"].shape[0] <= max(buckets):
            np.testing.assert_allclose(twin.run(r)[0], got, **_ACROSS_SHAPES)
    ws = warm.stats()
    assert ws["refused"] == 0 and ws["poisoned"] == 0, ws
    assert ws["warm_hits"] == len(buckets), ws
    return {"completed": s["completed"], "recompiles": s["recompiles"],
            "new_compiled_sigs": s["new_compiled_sigs"],
            "warm": {k: ws[k] for k in ("warm_hits", "warm_misses",
                                        "refused", "poisoned", "published")}}


# ------------------------------------------------------------------- main --

def _native_runtime_report():
    """Say which path the native data-feed library took on this machine:
    built with the image's g++, or absent (pure-Python reader)."""
    from paddle_tpu import runtime

    gxx = shutil.which("g++")
    lib = runtime.load("datafeed")
    return "g++=%s libdatafeed=%s" % (
        gxx or "absent", "built+loaded" if lib is not None
        else "not built (pure-Python path)")


def _timed(name, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    say("%s: PASS in %.1fs (wall, compile included) %s"
        % (name, time.perf_counter() - t0, json.dumps(out)))
    return out


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main():
    import functools

    import jax

    devs = jax.devices()
    d0 = devs[0]
    say("platform=%s device_kind=%s device_count=%d"
        % (d0.platform, d0.device_kind, len(devs)))
    if d0.platform != "tpu":
        print("chip_smoke: FAIL: this script needs a TPU and JAX found "
              "platform %r (%d x %s); it has no CPU mode"
              % (d0.platform, len(devs), d0.device_kind), file=sys.stderr)
        return 2

    from __graft_entry__ import dryrun_multichip
    from paddle_tpu import compile_cache
    from paddle_tpu.models import bert, deepfm

    cache_dir = compile_cache.place()
    entries0 = _cache_entries(cache_dir)
    say("compile cache: %s (%s), %d entries at start"
        % (cache_dir,
           "from JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "default, set in code", entries0))
    say("native runtime: " + _native_runtime_report())
    os.makedirs(OUT_DIR, exist_ok=True)
    t_all = time.perf_counter()

    _timed("kernels", kernels_phase)
    _timed("train", train_phase, devs,
           bert.bert_base_config(scan_unroll=12), batch=64, seq=512)
    ctr = deepfm.DeepFMConfig()
    head = dict(num_fields=ctr.num_fields, embed_dim=ctr.embed_dim)
    prog = _timed("program", program_phase, OUT_DIR, mlp_dims=ctr.mlp_dims,
                  **head)
    _timed("serve", serve_phase, prog["artifact"], **head)
    if len(devs) >= 4:
        # same widths; depth cut to 4 layers (2 per pipeline stage)
        # (every device must hold bytes while sharded state is live:
        # asserted inside dryrun_multichip)
        _timed("four_chip", dryrun_multichip, devs[:4],
               config=functools.partial(bert.bert_base_config, n_layers=4),
               seq=512)
    else:
        say("four_chip: not run (%d devices)" % len(devs))

    entries1 = _cache_entries(cache_dir)
    say("all phases passed in %.1fs; compile cache entries %d -> %d "
        "(%s)" % (time.perf_counter() - t_all, entries0, entries1,
                  "cache-warm: nothing added" if entries1 == entries0
                  else "cold: %d added" % (entries1 - entries0)))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
