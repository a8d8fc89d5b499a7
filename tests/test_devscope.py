"""monitor.devscope: the train path names its work with ``jax.named_scope``,
the trainers register the programs they dispatch, and ``scope_maps()`` reads
``instruction name -> op_name`` off the compiled text, on demand only."""

import gc
import re

import jax
import numpy as np
import pytest

from paddle_tpu.models import bert, resnet
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import optim
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.train import stack_batches

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@pytest.fixture(autouse=True)
def empty_registry():
    saved = devscope._programs[:]
    devscope._programs[:] = []
    yield
    devscope._programs[:] = saved


def _bert_batch(rng, b=4, s=32):
    return {"ids": rng.randint(0, 128, (b, s)).astype("int32"),
            "labels": rng.randint(0, 128, (b, s)).astype("int32"),
            "mask": (rng.rand(b, s) < 0.3).astype("float32")}


def _run_bert(dp=1, **cfg):
    tr = bert.build_bert_trainer(bert.bert_tiny_config(**cfg),
                                 MeshSpec(dp=dp), devices=jax.devices()[:dp])
    rng = np.random.RandomState(0)
    staged = stack_batches(tr.mesh, bert.batch_specs(),
                           [_bert_batch(rng), _bert_batch(rng)])
    losses = np.asarray(tr.run_steps(staged, 1e-3))
    assert np.isfinite(losses).all()
    return tr, staged


def _run_resnet(dp=1):
    tr = resnet.build_resnet_trainer(
        resnet.resnet_tiny_config(), MeshSpec(dp=dp),
        optimizer=optim.momentum(0.9), devices=jax.devices()[:dp])
    rng = np.random.RandomState(0)
    batch = {"image": rng.rand(4, 32, 32, 3).astype("float32"),
             "label": rng.randint(0, 10, (4,)).astype("int32")}
    assert np.isfinite(float(tr.step(batch, 1e-2)))
    return tr


def _classes(names):
    return {devscope.classify(op) for op in names.values()}


def test_classify_on_literal_op_names():
    table = {
        "jit(multi)/while/body/closed_call/jvp(lm_head)/dot_general":
            ("forward", "lm_head"),
        "jit(multi)/while/body/closed_call/transpose(jvp(lm_head))/lm_head/"
        "dot_general": ("backward", "lm_head"),
        # the innermost scope wins
        "jit(multi)/jvp()/while/body/closed_call/mlp/layer_norm/"
        "layer_norm_fwd/while/body/div": ("forward", "layer_norm"),
        "jit(multi)/transpose(jvp())/while/body/closed_call/attention/"
        "flash_bwd_fused/mul": ("backward", "attention"),
        # the scan's kernels inside the mixer's scope, and the mixer's own
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/mamba/"
        "mamba/selective_scan/selective_scan_bwd":
            ("backward", "selective_scan"),
        "jit(multi)/jvp()/while/body/closed_call/while/body/mamba/mamba/"
        "dot_general": ("forward", "mamba"),
        # the dual form's kernels inside the Mamba-2 branch's scope
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "mamba2/mamba2/ssd_scan/ssd_scan_bwd": ("backward", "ssd_scan"),
        "jit(multi)/jvp()/while/body/closed_call/mamba2/mamba2/"
        "dot_general": ("forward", "mamba2"),
        # the delta rule inside the KDA mixer's scope, under the layer's
        # checkpoint and its own, and the mixer's own
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "kda/kda/kda_chunk/checkpoint/while/body/dot_general":
            ("backward", "kda_chunk"),
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/kda/kda/kda_chunk/checkpoint/"
        "rematted_computation/exp": ("recompute", "kda_chunk"),
        "jit(multi)/jvp()/while/body/closed_call/kda/kda/dot_general":
            ("forward", "kda"),
        # no vocabulary word on the path
        "jit(multi)/while/body/closed_call/jvp()/while/body/closed_call":
            ("forward", None),
        "jit(multi)/while/body/closed_call/transpose(jvp())/concatenate":
            ("backward", None),
        "": ("forward", None),
        # the two phases that are scopes, also under a transform
        "jit(multi)/while/body/closed_call/optimizer/sqrt":
            ("optimizer", "optimizer"),
        "jit(step)/grad_sync/psum": ("grad_sync", "grad_sync"),
        # jax.checkpoint: the forward run again, and the body's own backward
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/attention/dot_general":
            ("recompute", "attention"),
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
        "transpose": ("backward", "mlp"),
        # a word of the vocabulary inside another name is none
        "jit(multi)/jvp(jit(convolve))/pooling/mul": ("forward", None),
        "jit(step)/jvp(bn)/jit(relu)/max": ("forward", "bn"),
        # a custom_vjp's backward kernel keeps its call's scope
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "retention/retention/power_retention_bwd": ("backward", "retention"),
        # a scope whose name ends in another's is its own; a scope inside
        # the MoE's is the innermost
        "jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "latent_attention/flash_bwd_dq": ("backward", "latent_attention"),
        "jit(multi)/jvp()/while/body/closed_call/moe/shared_expert/"
        "dot_general": ("forward", "shared_expert"),
        # the scan over the layers: its own slices are its, a layer's work
        # the layer's
        "jit(multi)/while/body/closed_call/transpose(jvp(layer_scan))/while/"
        "body/dynamic_update_slice": ("backward", "layer_scan"),
        "jit(multi)/while/body/closed_call/jvp(layer_scan)/while/body/"
        "closed_call/attention/dot_general": ("forward", "attention"),
    }
    for op_name, want in table.items():
        assert devscope.classify(op_name) == want, op_name
    assert set(devscope.PHASES) >= {w[0] for w in table.values()}
    assert len(set(devscope.VOCABULARY)) == len(devscope.VOCABULARY) == 37


@pytest.mark.parametrize("op_name, want", [
    # the output gate lies inside attention's scope and is its own
    ("jit(multi)/jvp()/while/body/closed_call/attention/attn_gate/logistic",
     ("forward", "attn_gate")),
    ("jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/attn_gate/mul",
     ("recompute", "attn_gate")),
    ("jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attention/attn_gate/mul", ("backward", "attn_gate")),
    # the gate's projection is attention's
    ("jit(multi)/jvp()/while/body/closed_call/attention/dot_general",
     ("forward", "attention")),
    # an output norm lies inside its branch's scope, whichever that is, and
    # is no ``layer_norm`` (the input norms are)
    ("jit(multi)/jvp()/while/body/closed_call/attention/post_norm/rsqrt",
     ("forward", "post_norm")),
    ("jit(multi)/transpose(jvp())/while/body/closed_call/checkpoint/moe/"
     "post_norm/mul", ("backward", "post_norm")),
    ("jit(multi)/transpose(jvp())/checkpoint/rematted_computation/mlp/"
     "post_norm/mul", ("recompute", "post_norm")),
    ("jit(multi)/jvp()/while/body/closed_call/moe/layer_norm/rsqrt",
     ("forward", "layer_norm")),
    # a looped stack's passes: the running sum of the stacked gradients and
    # the exits kept are the loop's own, the layers' scan inside a pass its
    # own, a layer's work the layer's, the gate's the gate's (at the end of
    # a pass, and in the loss outside the loop)
    ("jit(multi)/while/body/closed_call/transpose(jvp(loop_scan))/while/"
     "body/add_any", ("backward", "loop_scan")),
    ("jit(multi)/while/body/closed_call/jvp(loop_scan)/while/body/"
     "dynamic_update_slice", ("forward", "loop_scan")),
    ("jit(multi)/while/body/closed_call/jvp(loop_scan)/while/body/"
     "layer_scan/while/body/dynamic_slice", ("forward", "layer_scan")),
    ("jit(multi)/while/body/closed_call/transpose(jvp(loop_scan))/while/"
     "body/layer_scan/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", ("recompute", "mlp")),
    ("jit(multi)/while/body/closed_call/jvp(loop_scan)/while/body/"
     "exit_gate/dot_general", ("forward", "exit_gate")),
    ("jit(multi)/while/body/closed_call/transpose(jvp(exit_gate))/mul",
     ("backward", "exit_gate")),
])
def test_classify_the_gate_and_the_output_norms(op_name, want):
    assert devscope.classify(op_name) == want
    assert {"attn_gate", "post_norm", "loop_scan", "exit_gate"} \
        <= set(devscope.VOCABULARY)


def test_bert_program_that_ran_maps_every_scope_it_uses():
    tr, _ = _run_bert(dp=2)
    maps = devscope.scope_maps()
    # step() was never called: it has no map, and run_steps' names are its own
    assert list(maps) == ["bert.run_steps"]
    names = maps["bert.run_steps"]
    assert len(names) > 500
    got = _classes(names)
    for scope in ("embed", "attention", "mlp", "layer_norm"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("optimizer", "optimizer") in got
    assert ("grad_sync", "grad_sync") in got          # dp=2: a real psum
    assert not {s for _, s in got} - set(devscope.VOCABULARY) - {None}
    # the custom_vjp forward rule of the chunked vocabulary loss names
    # itself, inside its loop over row blocks
    assert any(re.search(
        r"jvp\(lm_head\)/lm_head/while/body/dot_general", op)
        for op in names.values())
    # the collective is among the named instructions
    assert any(n.startswith("all-reduce")
               and devscope.classify(op)[0] == "grad_sync"
               for n, op in names.items())


def test_resnet_step_maps_every_scope_it_uses():
    tr = _run_resnet(dp=2)
    maps = devscope.scope_maps()
    assert list(maps) == ["resnet.step"] and tr.multi_fn is not None
    got = _classes(maps["resnet.step"])
    for scope in ("conv", "bn", "pool", "fc", "loss"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    assert ("optimizer", "optimizer") in got
    assert ("grad_sync", "grad_sync") in got


def test_remat_layers_show_as_recompute():
    tr, _ = _run_bert(remat=True)
    got = _classes(devscope.scope_maps()["bert.run_steps"])
    assert {("recompute", "attention"), ("recompute", "mlp"),
            ("backward", "attention"), ("backward", "mlp"),
            ("forward", "attention"), ("forward", "mlp")} <= got


@pytest.fixture(scope="module")
def kimi_classes():
    """The (phase, scope) pairs of a tiny Kimi-Linear trainer's compiled
    ``run_steps`` under per-layer remat."""
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.parallel import decoder

    tr = kimi_linear.build_kimi_linear_trainer(
        kimi_linear.kimi_linear_tiny_config(remat=True), MeshSpec(dp=1),
        optimizer=optim.adamw(), seed=0, devices=jax.devices()[:1])
    ids = np.random.RandomState(0).randint(0, 256, (2, 2, 64)).astype("i4")
    tr.run_steps(stack_batches(tr.mesh, decoder.BATCH_SPECS,
                               [{"ids": i} for i in ids]), 1e-3)
    got = _classes(devscope.scope_maps()["kimi_linear.run_steps"])
    del tr
    gc.collect()
    return got


@pytest.mark.parametrize("scope", [devscope.KDA, devscope.KDA_CHUNK])
def test_the_kda_scopes_cover_forward_recompute_and_backward(kimi_classes,
                                                             scope):
    assert scope in devscope.VOCABULARY
    for phase in ("forward", "recompute", "backward"):
        assert (phase, scope) in kimi_classes, (phase, scope)


def test_registering_traces_lowers_and_compiles_nothing():
    import jax.monitoring

    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: seen.append(event)
        if event in COMPILE_EVENTS else None)
    tr, staged = _run_bert()
    assert "/jax/core/compile/backend_compile_duration" in seen
    assert [p[0] for p in devscope._programs] == ["bert.run_steps"]
    tr.run_steps(staged, 1e-3)      # the returned state retraces, once
    n = len(seen)
    tr.run_steps(staged, 1e-3)      # registered already: one attribute test
    assert len(devscope._programs) == 1 and len(seen) == n
    # what is kept holds no buffer
    avals = jax.tree.leaves(devscope._programs[0][2])
    assert all(isinstance(a, (jax.ShapeDtypeStruct, float)) for a in avals)
    # and asking finds the step's executable in the process: no new compile
    assert devscope.scope_maps()["bert.run_steps"]
    assert seen.count("/jax/core/compile/backend_compile_duration") == \
        seen[:n].count("/jax/core/compile/backend_compile_duration")


def test_both_programs_of_one_trainer_and_two_trainers_of_one_kind():
    tr, staged = _run_bert()
    rng = np.random.RandomState(1)
    tr.step(_bert_batch(rng), 1e-3)
    _run_bert()                               # dies at once: leaves no entry
    other, _ = _run_bert()
    maps = devscope.scope_maps()
    assert sorted(maps) == ["bert.run_steps", "bert.run_steps#2", "bert.step"]
    assert maps["bert.run_steps"] == maps["bert.run_steps#2"]
    assert not any("jit(multi)" in op for op in maps["bert.step"].values())
    del other


def test_a_dead_trainer_leaves_the_registry():
    tr, staged = _run_bert()
    keep = _run_resnet()
    assert len(devscope._programs) == 2
    del tr, staged
    gc.collect()
    assert list(devscope.scope_maps()) == ["resnet.step"]
    assert [p[0] for p in devscope._programs] == ["resnet.step"]
    del keep


def test_the_executable_is_fetched_once_whoever_asks_first():
    """``scope_maps()``, then the memory ledgers, then what is large: three
    readers, ONE ``lower().compile()`` a program, by the compile ledger's
    records (a lowering's trace is a record, a compile a ``backend`` one)."""
    import time

    from paddle_tpu.monitor import memscope
    from paddle_tpu.monitor.recompile import compile_ledger

    led = compile_ledger()
    tr, staged = _run_bert()
    tr.step(_bert_batch(np.random.RandomState(1)), 1e-3)
    t0 = time.perf_counter()
    maps = devscope.scope_maps()
    t1 = time.perf_counter()
    first = led.between(t0, t1)
    assert sum(1 for r in first if r["kind"] == "backend") <= len(maps) == 2
    ledgers = memscope.trainer_ledgers()
    largest = memscope.largest_values("bert.run_steps", 3)
    again = devscope.scope_maps()
    assert led.between(t1, time.perf_counter()) == []      # nothing asked
    assert sorted(ledgers) == sorted(maps) == sorted(again)
    assert len(largest) == 3
    kept = {id(c) for c in devscope.executables().values()}
    assert kept == {id(p[3]) for p in devscope._programs}
    # the kept executable does not keep its trainer
    del tr, staged
    gc.collect()
    assert devscope.executables() == {} and devscope._programs == []


def test_value_sizes_names_the_layer_scan_s_stacked_residual():
    """A two-layer scanned toy: the forward loop carries what it stacks for
    the backward pass, [2, ...] a value, under ``layer_scan``; the weights it
    only hands on are not its values."""
    import jax.numpy as jnp

    def loss(w, x):
        def layer(h, wl):
            return jnp.tanh(h @ wl), None

        with jax.named_scope(devscope.LAYER_SCAN):
            h, _ = jax.lax.scan(layer, x, w)
        return (h * h).sum()

    w = jnp.ones((2, 64, 64), jnp.float32) * 0.01
    x = jnp.ones((128, 64), jnp.float32)
    text = jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()
    values = devscope.value_sizes(text)
    assert values == sorted(values, key=lambda v: (-v[0], v[2]))
    carried = [v for v in values if "[" in v[2]]         # <while>[<index>]
    stacked = [v for v in carried if v[1].startswith("f32[2,128,64]")]
    assert stacked and stacked[0][0] == 2 * 128 * 64 * 4
    assert devscope.classify(stacked[0][3]) == ("forward", "layer_scan")
    # the stacked weights go through both loops unchanged: nobody's value
    forward = stacked[0][2].split("[")[0]
    assert not [v for v in carried if v[2].startswith(forward + "[")
                and v[1].startswith("f32[2,64,64]")]
    # the entry's own results are there, parameters and tuples are not
    entry = [v for v in values if "[" not in v[2]]
    assert entry and all(v[0] > 0 for v in entry)
    assert not [v for v in entry if v[2].startswith(("Arg_", "tuple"))]


def test_value_sizes_on_literal_text():
    text = """HloModule m

%body (p: (s32[], bf16[4,8]{1,0}, f32[2,4,8]{2,1,0})) -> (s32[], bf16[4,8]{1,0}, f32[2,4,8]{2,1,0}) {
  %p = (s32[], bf16[4,8]{1,0}, /*index=2*/f32[2,4,8]{2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %w = bf16[4,8]{1,0} get-tuple-element(%p), index=1
  %acc = f32[2,4,8]{2,1,0} get-tuple-element(%p), index=2
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  %upd = f32[2,4,8]{2,1,0} fusion(%acc, %w, %i), kind=kLoop, calls=%f, metadata={op_name="jit(f)/layer_scan/while/body/dynamic_update_slice"}
  ROOT %out = (s32[], bf16[4,8]{1,0}, f32[2,4,8]{2,1,0}) tuple(%next, %w, %upd)
}

ENTRY %main (a: bf16[4,8], b: f32[2,4,8]) -> f32[2,4,8] {
  %a = bf16[4,8]{1,0} parameter(0)
  %b = f32[2,4,8]{2,1,0:T(8,128)} parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[4,8]{1,0}, f32[2,4,8]{2,1,0}) tuple(%zero, %a, %b)
  %loop = (s32[], bf16[4,8]{1,0}, /*index=2*/f32[2,4,8]{2,1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/layer_scan/while"}
  %res = f32[2,4,8]{2,1,0} get-tuple-element(%loop), index=2
  ROOT %neg = f32[2,4,8]{2,1,0:T(8,128)} negate(%res), metadata={op_name="jit(f)/mlp/neg"}
}
"""
    # of the entry's loop: [1] is handed on, [2] starts from a parameter (an
    # argument the loop carries); its body is the step's own level
    assert devscope.value_sizes(text) == [
        (256, "f32[2,4,8]", "neg", "jit(f)/mlp/neg"),
        (256, "f32[2,4,8]", "upd",
         "jit(f)/layer_scan/while/body/dynamic_update_slice"),
        (4, "s32[]", "loop[0]", "jit(f)/layer_scan/while"),
        (4, "s32[]", "next", None)]      # its first user is the ROOT tuple
    # the same loop fed a value the program made: the accumulator is its own
    made = text.replace("tuple(%zero, %a, %b)", "tuple(%zero, %a, %acc0)") \
        .replace("  %zero = s32[] constant(0)\n",
                 "  %zero = s32[] constant(0)\n  %acc0 = f32[2,4,8]{2,1,0} "
                 "broadcast(%zero), dimensions={}\n")
    assert (256, "f32[2,4,8]", "loop[2]", "jit(f)/layer_scan/while") \
        in devscope.value_sizes(made)


def test_a_warm_callable_is_not_registered():
    class NoLower:                            # warm.WarmCallable's surface
        def __call__(self, *args):
            return args

    assert devscope.register("x", NoLower(), (1.0,)) is True
    assert devscope._programs == [] and devscope.scope_maps() == {}


def test_a_cache_keyed_without_metadata_serves_stale_names(tmp_path):
    """Why ``compile_cache.place()`` keys the cache on metadata: JAX hashes a
    program after stripping its debug info, so the same program WITHOUT its
    scopes (another commit's compile) is found under the same key, and the
    loaded executable's text has that commit's names."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    def compiled_text(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        return jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], -1)
        for in_key, entries, found in ((False, 1, False), (True, 2, True)):
            cache = tmp_path / str(in_key)
            cache.mkdir()
            jax.config.update(keys[0], str(cache))
            jax.config.update(keys[3], in_key)
            cc.reset_cache()
            assert "another_commit" in compiled_text("another_commit")
            assert ("lm_head" in compiled_text("lm_head")) is found
            assert len(list(cache.glob("jit_f-*"))) == entries
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
