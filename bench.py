"""Benchmark: train-step throughput on one TPU chip.

Default (`--model all`) emits one JSON line PER BASELINE config — resnet50,
nmt, deepfm, then bert LAST so a parser that keeps only the final line
still records the driver's headline metric: BERT-base pretraining
tokens/sec/chip, north-star >=50% MFU (BASELINE.json config 2).
`--model {bert,resnet50,nmt,deepfm}` runs a single config.

Each line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}.
For bert/resnet50, vs_baseline relates to the driver-set MFU/V100 targets
(the reference repo publishes no absolute numbers — BASELINE.md); for
nmt/deepfm the BASELINE criterion is parity, and vs_baseline now MEASURES it
each run: nmt trains a tiny copy-task model and reports beam-search decode
parity (1.0 = best beam reproduces the source), deepfm trains on a synthetic
learnable signal and reports AUC over the trained ids (1.0 = the sparse
lookup+update path learns).  All four lines record mfu (nmt/deepfm from the
compiled step's XLA cost analysis).  A config that throws prints
{"metric": <name>, "error": ...} instead, the remaining configs still run,
and the process exits non-zero.

Every line names the device it ran on (platform, device_kind,
device_count).  mfu and the roofline fields are device metrics: they exist
only when the device is an accelerator listed in PEAKS — a CPU run prints
neither, and an accelerator missing from PEAKS is an error, never a default.

All four configs run device-side multi-step loops (lax.scan over steps —
the train_from_dataset N-iterations-per-Run execution model), so host
dispatch latency amortizes across the scan the same way it would across a
real input pipeline.

DeepFM emits a SECOND line, deepfm_ctr_hostfed_examples_per_sec_per_chip:
the same autotuned step fed a fresh host batch every iteration through the
pipelined step engine (feed_pipe.DeviceFeedPipe + lazy fetches + in-flight
window).  PADDLE_TPU_BENCH_PIPE=0 strips the pipeline from that line
(inline convert + eager per-step fetch sync) for A/B measurement of the
overlap win.  The headline deepfm line's step variant is autotuned per run
across the four table-update plumbings in _deepfm_step_variants
(PADDLE_TPU_DEEPFM_VARIANT pins one by name).  Every line carrying an mfu
and a derived roofline ceiling also reports mfu_ceiling_rel (see _emit).
"""

import json
import time

import numpy as np


def model_flops_per_token(cfg, S):
    """Training (fwd+bwd = 3x fwd) matmul FLOPs per token.  Counts the LM
    head on every position; the count a job requires (the head on its
    predicted positions) is benchmark/flops/transformer_mlm_train.py."""
    E, L, F, V = cfg.hidden, cfg.n_layers, cfg.ffn_hidden, cfg.vocab_size
    per_layer_fwd = 8 * E * E + 4 * E * F + 4 * S * E   # qkv+proj, mlp, attn
    head_fwd = 2 * E * V                                 # tied LM head
    return 3 * (L * per_layer_fwd + head_fwd)


_RECORDS = []       # every metric line of this run, in print order


def _emit(rec):
    """Print one BENCH metric line AND remember it for the opt-in
    perf-ledger follow-up (``PADDLE_TPU_BENCH_LEDGER=1``: after the run,
    scripts/perf_ledger.py compares this run + the committed BENCH_r*.json
    history and prints the trend table; ``..._LEDGER_CHECK=1`` also gates
    — a >tolerance throughput/MFU drop fails the bench run).

    Every line that carries both an mfu and a derived roofline ceiling
    also gets ``mfu_ceiling_rel = mfu / ceiling`` — the ROADMAP item 3
    "done" metric (>=0.8 = the config harvests >=80% of its own measured
    memory-bandwidth bound) — so ceiling-relative progress is a first-
    class ledger field, not an after-the-fact division."""
    mfu, ceil = rec.get("mfu"), rec.get("mfu_ceiling_memroofline")
    if mfu and ceil:
        rec["mfu_ceiling_rel"] = round(mfu / ceil, 4)
    rec.update(_device_fields())
    _RECORDS.append(rec)
    print(json.dumps(rec), flush=True)


def _device_fields():
    """What the line ran on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _ledger_followup():
    import os
    import sys
    import tempfile

    if not os.environ.get("PADDLE_TPU_BENCH_LEDGER") or not _RECORDS:
        return 0
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "scripts"))
    from _pt_path_load import load_pt_module

    ledger = load_pt_module("scripts", "perf_ledger.py")
    cur = os.path.join(tempfile.mkdtemp(prefix="bench_ledger_"),
                       "bench_current.jsonl")
    with open(cur, "w") as f:
        for rec in _RECORDS:
            f.write(json.dumps(rec) + "\n")
    argv = ["--history-dir", repo, "--current", cur]
    if os.environ.get("PADDLE_TPU_BENCH_LEDGER_CHECK"):
        argv.append("--check")
    rc = ledger.main(argv)
    if rc and os.environ.get("PADDLE_TPU_BENCH_LEDGER_CHECK"):
        print("bench: perf_ledger --check failed (rc=%d)" % rc,
              file=sys.stderr, flush=True)
        return rc
    return 0


def _finite(x):
    """NaN/inf are not valid JSON; report null so the line stays parseable."""
    return round(x, 4) if np.isfinite(x) else None


def _compile_probe(lower_fn):
    """Measured restart cost of this config's own step module: ``compile_ms``
    is the cold AOT lower+XLA-compile wall, ``warm_compile_ms`` the
    serialize -> deserialize round trip a restarted process pays through
    the WarmStart executable store instead (paddle_tpu/warm.py
    measure_roundtrip_ms).  Pays one extra compile of the module — only
    ever called from the opt-in telemetry path."""
    from paddle_tpu import warm as _warm

    t0 = time.perf_counter()
    compiled = lower_fn().compile()
    cold = (time.perf_counter() - t0) * 1e3
    out = {"compile_ms": round(cold, 1)}
    wm = _warm.measure_roundtrip_ms(compiled)
    if wm is not None:
        out["warm_compile_ms"] = round(wm, 2)
    # MemScope: the probed module's own memory ledger — the MODEL half of
    # the peak-vs-predicted delta for jit-driven configs that never pass
    # the executor's ledger hook
    from paddle_tpu.monitor import memscope as _memscope

    model = _memscope.model_bytes(_memscope.program_ledger(compiled))
    if model:
        out["hbm_model_bytes"] = int(model)
    return out


def _telemetry(metric, steps, seconds, batch, compile_probe=None):
    """Per-config telemetry block for the BENCH json line, active only when
    the monitor subsystem is on (PADDLE_TPU_BENCH_MONITOR=1 in main, or an
    enclosing monitor.enable()): records the measured per-step time into the
    registry/timeline and summarizes compiles/recompiles + the memory
    watermark so a bench regression comes with its explanation attached.
    Returns {} when monitoring is off — the headline line shape is
    unchanged by default.

    compile_probe: how this line's ``compile_ms`` (cold) and
    ``warm_compile_ms`` (WarmStart deserialize) are measured — a callable
    returning the step module's Lowered (probed via _compile_probe), a
    pre-measured dict of those fields, or None (executor-driven configs:
    deltas of the process-wide warm.stats() compile/deserialize clocks,
    absent when the config compiled nothing — perf_ledger tolerates
    absence, same idiom as mfu_ceiling_rel)."""
    from paddle_tpu import monitor
    from paddle_tpu import warm as _warm

    mon = monitor.active()
    if mon is None:
        return {}
    wstats = _warm.stats()
    wbase, _telemetry._warm_seen = _telemetry._warm_seen, wstats
    step_ms = seconds / max(steps, 1) * 1e3
    mon.registry.histogram("bench.step_ms", config=metric).observe(step_ms)
    mon.timeline.emit("bench_step", bench=metric, steps=steps,
                      step_ms=round(step_ms, 4), batch=batch)
    snap = monitor.sample_memory(mon.registry, mon.timeline)
    mon.export_prometheus()
    mon.timeline.flush()   # partial bench runs must still leave their events
    # compiles/recompiles are process-lifetime totals; report the DELTA
    # since the previous config's line so each config owns its own churn
    compiles = mon.recompiles.total_compiles
    recompiles = mon.recompiles.total_recompiles
    base = _telemetry._seen
    _telemetry._seen = (compiles, recompiles)
    tele = {
        "step_ms": round(step_ms, 3),
        "compiles": compiles - base[0],
        "recompiles": recompiles - base[1],
        "mem_live_bytes": snap.get("live_bytes"),
        "monitor_dir": mon.out_dir,
    }
    # XLA cost introspection (executor compile-miss hook): the heaviest
    # compiled program's analyzed FLOPs, and the achieved FLOPs/s at the
    # measured step time — the bench line's own model-flops estimate now
    # comes with XLA's independent count next to it
    cost_rows = [r for r in mon.registry.snapshot()
                 if r["name"] == "monitor.cost.flops" and r["value"] > 0]
    if cost_rows:
        top = max(cost_rows, key=lambda r: r["value"])
        tele["xla_flops_per_step"] = top["value"]
        tele["xla_program"] = top["labels"].get("program")
        if step_ms > 0:
            tele["xla_flops_per_sec"] = round(
                top["value"] / (step_ms / 1e3), 3)
    # restart cost (WarmStart): cold compile_ms + warm_compile_ms for the
    # perf_ledger compile-latency trend
    if callable(compile_probe):
        tele.update(_compile_probe(compile_probe))
    elif isinstance(compile_probe, dict):
        tele.update(compile_probe)
    else:
        dc = wstats["compile_ms"] - wbase.get("compile_ms", 0.0)
        if dc > 0:
            tele["compile_ms"] = round(dc, 1)
        dd = wstats["deserialize_ms"] - wbase.get("deserialize_ms", 0.0)
        if dd > 0:
            tele["warm_compile_ms"] = round(dd, 2)
    # MemScope: measured device-memory high-water mark next to the compiled
    # ledger's own prediction, so every bench line says how full the chip
    # got AND how far off the model was.  peak_hbm_bytes prefers the
    # allocator's peak_bytes_in_use; backends without allocator stats (the
    # CPU fallback) report the live-array watermark instead — still a
    # trendable lower-is-better number.  The model is the max temp+output
    # requirement over the programs THIS config compiled (the ledgers
    # recorded since the previous line), perf_ledger idiom:
    # tolerated-absent when nothing compiled or the backend cannot say.
    dev_peaks = [st.get("peak_bytes_in_use", st.get("bytes_in_use"))
                 for st in (snap.get("devices") or {}).values()]
    dev_peaks = [p for p in dev_peaks if p]
    # the allocator peak is PROCESS-monotone: a small config after a big
    # one inherits the big one's watermark.  Report it (it is the honest
    # high-water at this line's end) but compute the model-vs-measured
    # delta only when THIS line raised it — comparing an inherited peak
    # against this line's own model would be noise.  The stat-less (CPU)
    # fallback uses the CURRENT live bytes, which are per-line by nature.
    peak = max(dev_peaks) if dev_peaks else snap.get("live_bytes")
    prev_peak = _telemetry._peak_seen
    fresh_peak = bool(peak) and (not dev_peaks or peak > prev_peak)
    if dev_peaks:
        _telemetry._peak_seen = max(prev_peak, peak)
    if peak:
        tele["peak_hbm_bytes"] = int(peak)
    from paddle_tpu.monitor import memscope as _memscope

    model = tele.get("hbm_model_bytes")
    if model is None:
        # executor-driven configs: the model comes from the ledgers THIS
        # config's compiles recorded — a config whose programs were all
        # cache hits gets NO model (tolerated-absent), never another
        # config's
        leds = _memscope.ledgers()
        new = leds[_telemetry._ledgers_seen:]
        _telemetry._ledgers_seen = len(leds)
        models = [_memscope.model_bytes(led) for _, led in new]
        models = [m for m in models if m]
        if models:
            model = int(max(models))
            tele["hbm_model_bytes"] = model
    if model and peak and fresh_peak:
        tele["hbm_model_delta"] = round(float(peak) / model - 1.0, 4)
    return {"telemetry": tele}


_telemetry._seen = (0, 0)
_telemetry._warm_seen = {}
_telemetry._ledgers_seen = 0
_telemetry._peak_seen = 0


RESNET50_FLOPS_PER_IMAGE = 3 * 4.09e9   # fwd 4.09 GFLOP @224x224, train = 3x

# Published per-chip peaks, keyed by the device_kind string JAX reports
# (the v5e's was read off the chip: chip_smoke.py prints it).  Source:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM.
# A device that is not listed has no mfu and no roofline: _env raises.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _roofline_from(flops, nbytes, peaks):
    """Memory-roofline ceiling fields from analyzed (flops, bytes):
    ceiling = min(1, AI * BW / peak) with AI = flops / bytes-accessed.
    Returns {} when any ingredient is missing — honest-or-absent."""
    if not peaks or not flops or not nbytes:
        return {}
    if flops <= 0 or nbytes <= 0:
        return {}
    bw, peak = peaks["hbm_bytes_per_s"], peaks["bf16_flops"]
    ai = flops / nbytes
    return {
        "mfu_ceiling_memroofline": round(min(1.0, ai * bw / peak), 4),
        "roofline_ai_flops_per_byte": round(ai, 2),
        "roofline_hbm_gbps": round(bw / 1e9, 1),
    }


def _roofline(cost_fn, peaks):
    """Memory-roofline MFU ceiling DERIVED from the compiled step's own
    bytes/FLOPs arithmetic intensity (XLA cost_analysis of the very module
    being benchmarked) instead of a hardcoded constant that silently lies
    off the config it was measured on.  AI is a ratio, so analyzing a
    multi-step scan needs no per-step normalization.  {} off-accelerator.
    cost_fn analyzes the COMPILED module (a compile-cache hit): on the TPU
    a Lowered's cost_analysis() is None."""
    if not peaks:
        return {}                  # don't pay the lowering to discard it
    cost = cost_fn()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return _roofline_from(float(cost.get("flops") or 0.0),
                          float(cost.get("bytes accessed") or 0.0), peaks)


def _env():
    """(devices, on_accelerator, peaks): peaks is the device's PEAKS row,
    None on the CPU (no device metric is derived there)."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        return devs, False, None
    kind = devs[0].device_kind
    if kind not in PEAKS:
        raise RuntimeError(
            "no published peaks for device_kind %r (platform %s): add its "
            "row to bench.PEAKS with the source" % (kind, devs[0].platform))
    return devs, True, PEAKS[kind]


def bench_bert():
    devs, on_tpu, peaks = _env()
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import MeshSpec, optim
    from paddle_tpu.parallel.train import stack_batches

    if on_tpu:
        # scan_unroll: unrolling the layer scan turns the per-layer dynamic
        # param slices into static ones (+6% MFU measured in r5's batch
        # sweep); B=64 is the sweet spot (96 hits a compiler limit,
        # 128+remat trades the win back for recompute)
        cfg = bert.bert_base_config(scan_unroll=12)
        B, S, N, reps = 64, 512, 10, 3
    else:
        cfg = bert.bert_tiny_config()
        B, S, N, reps = 8, 32, 2, 1

    trainer = bert.build_bert_trainer(
        cfg, MeshSpec(1, 1, 1), optimizer=optim.lamb(), devices=devs[:1]
    )
    rng = np.random.RandomState(0)

    def mk_batch():
        return {
            "ids": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32),
            "labels": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32),
            "mask": np.ones((B, S), np.float32),
        }

    batches = stack_batches(trainer.mesh, bert.batch_specs(),
                            [mk_batch() for _ in range(N)])

    # warmup/compile; float() is a hard host sync
    losses = trainer.run_steps(batches, 1e-4)
    float(losses[-1])

    t0 = time.perf_counter()
    for _ in range(reps):
        losses = trainer.run_steps(batches, 1e-4)
    # the state chain makes the last loss depend on every step
    float(losses[-1])
    dt = time.perf_counter() - t0

    steps = N * reps
    tokens_per_sec = B * S * steps / dt
    device_metrics = {}
    if peaks:
        mfu = (tokens_per_sec * model_flops_per_token(cfg, S)
               / peaks["bf16_flops"])
        device_metrics = {
            "vs_baseline": round(mfu / 0.50, 4),
            "mfu": round(mfu, 4),
            **_roofline(
                lambda: trainer.multi_fn.lower(
                    trainer.state, batches,
                    1e-4).compile().cost_analysis(), peaks),
        }
    _emit({
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        **device_metrics,
        "scan_unroll": cfg.scan_unroll,
        "batch": B,
        "seq": S,
        "loss": _finite(float(losses[-1])),
        **_telemetry("bert", steps, dt, B,
                     compile_probe=lambda: trainer.multi_fn.lower(
                         trainer.state, batches, 1e-4)),
    })


def bench_resnet50():
    devs, on_tpu, peaks = _env()
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import MeshSpec, optim
    from paddle_tpu.parallel.train import stack_batches

    if on_tpu:
        cfg = resnet.resnet50_config(dtype="bfloat16")
        B, N, reps = 128, 25, 2
        flops_per_image = RESNET50_FLOPS_PER_IMAGE
    else:
        cfg = resnet.resnet_tiny_config()
        B, N, reps = 8, 2, 1
        flops_per_image = 3 * 2 * 1e6

    trainer = resnet.build_resnet_trainer(cfg, MeshSpec(1, 1, 1),
                                          optimizer=optim.momentum(0.9),
                                          devices=devs[:1])
    rng = np.random.RandomState(0)
    size = cfg.image_size

    def mk_batch():
        return {
            "image": rng.rand(B, size, size, 3).astype(np.float32),
            "label": rng.randint(0, cfg.num_classes, (B,)).astype(np.int32),
        }

    batches = stack_batches(trainer.mesh, resnet.BATCH_SPECS,
                            [mk_batch() for _ in range(N)])
    if on_tpu:
        # stage images in bf16: halves the staged-batch HBM footprint and the
        # per-step input read; the model casts to its compute dtype anyway
        import jax.numpy as jnp
        batches = dict(batches, image=batches["image"].astype(jnp.bfloat16))

    losses = trainer.run_steps(batches, 1e-2)
    float(losses[-1])

    t0 = time.perf_counter()
    for _ in range(reps):
        losses = trainer.run_steps(batches, 1e-2)
    float(losses[-1])
    dt = time.perf_counter() - t0

    steps = N * reps
    images_per_sec = B * steps / dt
    # BASELINE.md criterion for this config: "within 5% of Paddle's published
    # V100 throughput" — the era's published ResNet-50 fp16 number was ~1000
    # images/s on a V100, so vs_baseline = images_per_sec / 1000.
    #
    # MFU context: ResNet-50/224 bf16 is HBM-bound, not MXU-bound, so mfu
    # reads against the memory-roofline ceiling, DERIVED per run by
    # _roofline from this compiled step's own analyzed bytes/FLOPs
    # arithmetic intensity.  Cost analysis happens after the timed region.
    device_metrics = {}
    if peaks:
        device_metrics = {
            "mfu": round(images_per_sec * flops_per_image
                         / peaks["bf16_flops"], 4),
            **_roofline(
                lambda: trainer.multi_fn.lower(
                    trainer.state, batches, 1e-2).compile().cost_analysis(),
                peaks),
        }
    _emit({
        "metric": "resnet50_imagenet_images_per_sec_per_chip",
        "value": round(images_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": round(images_per_sec / 1000.0, 4),
        **device_metrics,
        "batch": B,
        "image_size": size,
        "loss": _finite(float(losses[-1])),
        **_telemetry("resnet50", steps, dt, B,
                     compile_probe=lambda: trainer.multi_fn.lower(
                         trainer.state, batches, 1e-2)),
    })


def _run_sgd_bench(metric, unit, loss_fn, params, batch, iters, lr,
                   per_step, batch_size, peaks=None, parity_fn=None,
                   step_fn=None, extra=None):
    """Shared harness for the parity-criterion configs (nmt/deepfm): jitted
    SGD steps, params chained so every step depends on the previous, one
    float() sync at the end, one JSON line out.

    vs_baseline is the config's BASELINE criterion measured for real by
    `parity_fn` (decode parity for nmt, AUC-vs-threshold for deepfm) — not a
    hardcoded constant.  mfu comes from the compiled step's own FLOP count
    (XLA cost analysis) when available.  `step_fn` overrides the default
    plain-SGD step (deepfm passes its autotuned sparse-update variant);
    `extra` fields are merged into the JSON line."""
    import jax

    if step_fn is None:
        def step_fn(params, batch):
            loss, g = jax.value_and_grad(loss_fn)(params, batch)
            new = jax.tree.map(lambda p, gr: p - lr * gr.astype(p.dtype),
                               params, g)
            return new, loss

    # FLOPs + bytes from the single step's AOT compile: flops feed mfu,
    # and the flops/bytes arithmetic intensity feeds the DERIVED memory-
    # roofline ceiling (_roofline_from) — the DeepFM/NMT lines now carry
    # the same honest ceiling the resnet line got in r07, so their
    # mfu_ceiling_rel is measured, not asserted
    compile_fields = {}
    t_c = time.perf_counter()
    compiled = jax.jit(step_fn).lower(params, batch).compile()
    # the cost-analysis compile doubles as this line's restart-cost
    # probe: cold compile_ms + the WarmStart deserialize round trip
    # (no extra compile is paid — the probe rides what was already
    # being built)
    compile_fields["compile_ms"] = round(
        (time.perf_counter() - t_c) * 1e3, 1)
    from paddle_tpu import warm as _warm_mod

    wm = _warm_mod.measure_roundtrip_ms(compiled)
    if wm is not None:
        compile_fields["warm_compile_ms"] = round(wm, 2)
    from paddle_tpu.monitor import memscope as _memscope

    model = _memscope.model_bytes(_memscope.program_ledger(compiled))
    if model:
        compile_fields["hbm_model_bytes"] = int(model)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops_per_step = float(cost.get("flops", 0.0)) or None
    bytes_per_step = float(cost.get("bytes accessed", 0.0)) or None

    # device-side multi-step loop (same policy as the bert/resnet trainers'
    # run_steps: host dispatch amortizes across the scan the way it would
    # across a real input pipeline)
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run_n(params, batch):
        def body(p, _):
            p, loss = step_fn(p, batch)
            return p, loss
        return lax.scan(body, params, None, length=iters)

    p, losses = run_n(params, batch)
    loss = float(losses[-1])
    t0 = time.perf_counter()
    for _ in range(2):
        p, losses = run_n(p, batch)
    loss = float(losses[-1])
    dt = (time.perf_counter() - t0) / (2 * iters)

    rec = {
        "metric": metric,
        "value": round(per_step / dt, 1),
        "unit": unit,
        "vs_baseline": 1.0 if np.isfinite(loss) else 0.0,
        "step_ms": round(dt * 1000, 2),
        "batch": batch_size,
        "loss": _finite(loss),
    }
    if flops_per_step and peaks:
        rec["mfu"] = round(flops_per_step / dt / peaks["bf16_flops"], 4)
        rec.update(_roofline_from(flops_per_step, bytes_per_step, peaks))
    if parity_fn is not None:
        name, value = parity_fn()
        rec[name] = round(float(value), 4)
        rec["vs_baseline"] = round(float(value), 4) if np.isfinite(loss) else 0.0
    if extra:
        rec.update(extra)
    rec.update(_telemetry(metric, 2 * iters, dt * 2 * iters, batch_size,
                          compile_probe=compile_fields))
    _emit(rec)


def bench_nmt():
    """Transformer-base NMT train-step throughput (BASELINE config 4).
    vs_baseline is MEASURED beam-search decode parity via the shared
    models/parity.py recipe (1.0 = best beam reproduces the source)."""
    import jax
    import jax.numpy as jnp

    devs, on_tpu, peaks = _env()
    from paddle_tpu.models import transformer_nmt as nmt

    if on_tpu:
        # scan_unroll=n_layers: same static-slice win as BERT (+66% tok/s
        # measured r5); B=128 is the throughput peak (256 regresses)
        cfg = nmt.NMTConfig(dtype="bfloat16", scan_unroll=6)
        B, Ss, St, iters = 128, 128, 128, 12
    else:
        cfg = nmt.nmt_tiny_config()
        B, Ss, St, iters = 4, 8, 8, 2

    params = nmt.init_nmt_params(jax.random.PRNGKey(0), cfg)

    # draw the batch from the wmt16 corpus loader (real archive when cached
    # under DATA_HOME, deterministic synthetic otherwise) — BASELINE's NMT
    # config is wmt16-shaped variable-length text, not uniform random ids
    def wmt16_batch():
        from paddle_tpu.datasets import wmt16 as wmt16_ds

        src = np.zeros((B, Ss), np.int32)
        tin = np.zeros((B, St), np.int32)
        tout = np.zeros((B, St), np.int32)
        smask = np.zeros((B, Ss), np.float32)
        tmask = np.zeros((B, St), np.float32)
        it = iter(wmt16_ds.train(cfg.src_vocab, cfg.tgt_vocab)())
        samples = []
        while len(samples) < B:
            try:
                samples.append(next(it))
            except StopIteration:
                it = iter(wmt16_ds.train(cfg.src_vocab, cfg.tgt_vocab)())
        for i, (s, t, tn) in enumerate(samples):
            s, t, tn = s[:Ss], t[:St], tn[:St]
            src[i, :len(s)] = s
            tin[i, :len(t)] = t
            tout[i, :len(tn)] = tn
            smask[i, :len(s)] = 1.0
            tmask[i, :len(tn)] = 1.0
        return {"src_ids": jnp.asarray(src), "src_mask": jnp.asarray(smask),
                "tgt_in": jnp.asarray(tin), "tgt_out": jnp.asarray(tout),
                "tgt_mask": jnp.asarray(tmask)}

    batch = wmt16_batch()
    def decode_parity():
        """BASELINE criterion: beam-search decode parity, measured by the
        shared recipe (models/parity.py) that tests/test_models.py asserts
        on; 1.0 = best beam reproduces the source."""
        from paddle_tpu.models.parity import nmt_copy_decode_parity

        return "decode_parity", nmt_copy_decode_parity()

    _run_sgd_bench("transformer_nmt_train_tokens_per_sec_per_chip",
                   "tokens/s", lambda p, b: nmt.nmt_loss(p, b, cfg),
                   params, batch, iters, 1e-4, B * (Ss + St), B,
                   peaks=peaks, parity_fn=decode_parity)


def _deepfm_step_variants(cfg, lr):
    """The DeepFM SGD step, three table-update plumbings — SAME math (a
    dense table gradient IS the scatter-add of the per-occurrence row
    gradients, so every variant applies identical updates mod f32 summation
    order), different sparse-traffic shape:

    - dense:  value_and_grad over the full params tree (r05 baseline) —
      two [V,*] dense grads, each a duplicate-laden scatter, two gathers;
    - fused:  one [V, D+1] table (embedding ‖ first-order weight,
      models/deepfm.fuse_tables) — ONE gather + ONE scatter, halving the
      row traffic of the scatter-bound step;
    - rows:   fused table + differentiate w.r.t. the GATHERED rows
      (deepfm_loss_from_rows) and apply via sparse.merge_rows: the update
      scatters sorted-UNIQUE rows with the compiler hints
      (indices_are_sorted/unique_indices) instead of 319k duplicates;
    - segment: the rows plumbing with the dedup done by the Pallas
      deduped segment-sum kernel (kernels/segment_update.py — one
      blockwise MXU sweep over the sorted row gradients instead of XLA's
      segment_sum lowering), one drop-mode scatter per unique row.

    bench.py autotunes across them per run (the chip decides, not a
    hardcoded guess) and reports the winner as step_variant;
    PADDLE_TPU_DEEPFM_VARIANT pins a variant by name and skips the
    autotune (_autotune_deepfm_step)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import deepfm
    from paddle_tpu.sparse import merge_rows

    D = cfg.embed_dim

    def _head_side(params):
        return {"mlp": params["mlp"], "bias": params["bias"]}

    def _apply_head(params, g_head):
        upd = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                           _head_side(params), g_head)
        return upd["mlp"], upd["bias"]

    def dense(params, batch):
        loss, g = jax.value_and_grad(
            lambda p: deepfm.deepfm_loss(p, batch, cfg))(params)
        new = jax.tree.map(lambda p, gr: p - lr * gr.astype(p.dtype),
                           params, g)
        return new, loss

    def fused(params, batch):
        f = deepfm.fuse_tables(params)
        loss, (g_f, g_head) = jax.value_and_grad(
            lambda f_, h: deepfm.deepfm_loss_fused(h, f_, batch, cfg),
            argnums=(0, 1))(f, _head_side(params))
        out = deepfm.split_tables(params, f - lr * g_f.astype(f.dtype))
        out["mlp"], out["bias"] = _apply_head(params, g_head)
        return out, loss

    def rows(params, batch):
        f = deepfm.fuse_tables(params)
        ids = batch["feat_ids"].reshape(-1)
        gathered = f[ids]                                  # [N, D+1]
        shape3 = batch["feat_ids"].shape + (D + 1,)
        loss, (g_rows, g_head) = jax.value_and_grad(
            lambda rv, h: deepfm.deepfm_loss_from_rows(
                h, rv.reshape(shape3), batch["label"], cfg),
            argnums=(0, 1))(gathered, _head_side(params))
        # via="xla" pinned: this scatter promises indices_are_sorted, which
        # only the compacted XLA merge layout satisfies (the kernel layout
        # is the separate 'segment' variant below)
        mrows, mvals = merge_rows(ids, g_rows, f.shape[0], via="xla")
        f = f.at[mrows].add((-lr * mvals).astype(f.dtype), mode="drop",
                            indices_are_sorted=True, unique_indices=True)
        out = deepfm.split_tables(params, f)
        out["mlp"], out["bias"] = _apply_head(params, g_head)
        return out, loss

    def segment(params, batch):
        from paddle_tpu.kernels.segment_update import dedup_segment_sum

        f = deepfm.fuse_tables(params)
        ids = batch["feat_ids"].reshape(-1)
        gathered = f[ids]                                  # [N, D+1]
        shape3 = batch["feat_ids"].shape + (D + 1,)
        loss, (g_rows, g_head) = jax.value_and_grad(
            lambda rv, h: deepfm.deepfm_loss_from_rows(
                h, rv.reshape(shape3), batch["label"], cfg),
            argnums=(0, 1))(gathered, _head_side(params))
        mrows, mvals = dedup_segment_sum(ids, g_rows, f.shape[0])
        # kernel layout: unique rows at their FIRST sorted position (not
        # compacted), so the row vector is not sorted — unique still holds
        f = f.at[mrows].add((-lr * mvals).astype(f.dtype), mode="drop",
                            unique_indices=True)
        out = deepfm.split_tables(params, f)
        out["mlp"], out["bias"] = _apply_head(params, g_head)
        return out, loss

    return {"dense": dense, "fused": fused, "rows": rows,
            "segment": segment}


def _autotune_deepfm_step(variants, params, batch, tune_iters):
    """Time a short scanned loop of each variant and return (name, step_fn,
    {name: ms}).  A variant that fails to compile/run cannot win, and is
    REPORTED, not skipped: its entry in the timing record is
    ``"failed: <error>"`` (the bench line carries it as autotune_step_ms)
    and the error goes to stderr.

    ``PADDLE_TPU_DEEPFM_VARIANT=<name>`` pins the winner and skips the
    timing loop entirely (the ROADMAP "pin the autotune winner once chip
    access is interactive" knob): the named variant runs with
    ``{name: "pinned"}`` as its timing record; an unknown name raises,
    listing the valid variants."""
    import jax
    import os
    import sys
    from jax import lax

    pinned = os.environ.get("PADDLE_TPU_DEEPFM_VARIANT", "").strip()
    if pinned:
        if pinned not in variants:
            raise ValueError(
                "PADDLE_TPU_DEEPFM_VARIANT=%r is not a step variant "
                "(valid: %s)" % (pinned, ", ".join(sorted(variants))))
        return pinned, variants[pinned], {pinned: "pinned"}

    timings = {}
    best = None
    last_err = None
    for name, step in variants.items():
        @jax.jit
        def run_n(p, b, _step=step):
            def body(p_, _):
                p_, loss = _step(p_, b)
                return p_, loss
            return lax.scan(body, p, None, length=tune_iters)

        try:
            p, losses = run_n(params, batch)
            float(losses[-1])                      # compile + warm
            t0 = time.perf_counter()
            p, losses = run_n(p, batch)
            float(losses[-1])
            dt = (time.perf_counter() - t0) / tune_iters
        except Exception as e:   # noqa: BLE001 — one variant, reported
            last_err = e
            timings[name] = "failed: %s" % str(e)[:200]
            print("deepfm step variant %r failed: %s" % (name, e),
                  file=sys.stderr, flush=True)
            continue
        timings[name] = round(dt * 1e3, 3)
        if best is None or dt < best[2]:
            best = (name, step, dt)
    if best is None:
        # every variant failed: surface the real cause, not a TypeError
        raise RuntimeError(
            "deepfm step autotune: all variants failed") from last_err
    return best[0], best[1], timings


def _bench_deepfm_hostfed(cfg, params0, step_fn, variant, B, iters, lr):
    """End-to-end host-fed DeepFM line: a FRESH numpy batch every step
    streams through the pipelined step engine — DeviceFeedPipe converts +
    device_puts batch k+1 on a background thread while step k runs, fetches
    stay lazy, and the in-flight window (K=2) bounds host run-ahead.
    PADDLE_TPU_BENCH_PIPE=0 strips the pipeline (inline convert +
    device_put + eager per-step fetch sync — the pre-pipe Executor.run
    behavior) so one env flip A/Bs the overlap win on the same step."""
    import os

    import jax

    from paddle_tpu.feed_pipe import DeviceFeedPipe, InFlightWindow

    use_pipe = os.environ.get("PADDLE_TPU_BENCH_PIPE", "1").strip() != "0"
    rng = np.random.RandomState(1)

    def mk_batch(_k):
        return {
            "feat_ids": rng.randint(
                0, cfg.num_features, (B, cfg.num_fields)).astype(np.int32),
            "label": rng.randint(0, 2, (B,)).astype(np.float32),
        }

    dev = jax.devices()[0]

    def convert(b):
        return {k: jax.device_put(v, dev) for k, v in b.items()}

    jstep = jax.jit(step_fn, donate_argnums=(0,))
    import jax.numpy as jnp

    # donation consumes the params tree: work on a private copy so the
    # caller's params survive for any later config
    params, loss = jstep(jax.tree.map(jnp.array, params0),
                         convert(mk_batch(-1)))
    float(loss)                                    # compile + warm

    # the inline mode syncs every step; keep its A/B run short so
    # PADDLE_TPU_BENCH_PIPE=0 stays usable
    steps = iters if use_pipe else max(iters // 4, 8)

    # long-run fault-tolerance mode (PADDLE_TPU_BENCH_CKPT=1): the same
    # host-fed loop runs under a CheckpointPolicy through
    # parallel.train.TrainLoop — boundary saves ride the shard/COMMIT
    # protocol, SIGTERM takes the agreed-boundary preemption path, and a
    # rerun with the same PADDLE_TPU_BENCH_CKPT_DIR resumes at the exact
    # step.  Default off: the headline line is byte-identical without it.
    ckpt_policy = ckpt_extra = None
    if os.environ.get("PADDLE_TPU_BENCH_CKPT"):
        import tempfile

        from paddle_tpu import ft, monitor as _mon_mod

        steps = (int(os.environ.get("PADDLE_TPU_BENCH_CKPT_STEPS", "") or 0)
                 or 2 * steps)                     # the LONG in long-run
        ck_dir = (os.environ.get("PADDLE_TPU_BENCH_CKPT_DIR")
                  or tempfile.mkdtemp(prefix="bench_ckpt_"))
        every = (int(os.environ.get("PADDLE_TPU_BENCH_CKPT_EVERY", "") or 0)
                 or max(steps // 4, 1))
        ckpt_policy = ft.CheckpointPolicy(
            ck_dir, every_steps=every, asynchronous=True, keep=2,
            resume=True)
        saves0 = _mon_mod.default_registry().counter("ft.ckpt.saves").value

    src = (mk_batch(k) for k in range(steps))
    t0 = time.perf_counter()
    if use_pipe:
        pipe = DeviceFeedPipe(src, convert=convert, name="bench_deepfm_pipe")
        window = InFlightWindow()
        if ckpt_policy is not None:
            from paddle_tpu.parallel.train import TrainLoop

            loop = TrainLoop(jstep, checkpoint=ckpt_policy, window=window)
            params, _n = loop.run(params, pipe)
            # last_aux is None when the resume checkpoint already covered
            # every step (a rerun of a finished long-run dir): no new loss
            loss = (loop.last_aux if loop.last_aux is not None
                    else float("nan"))
        else:
            for b in pipe:
                params, loss = jstep(params, b)
                window.admit(loss)                 # bounded async dispatch
            window.drain()
        loss_v = float(loss)
    else:
        if ckpt_policy is not None:
            from paddle_tpu.parallel.train import TrainLoop

            loop = TrainLoop(lambda p, b: jstep(p, convert(b)),
                             checkpoint=ckpt_policy)
            params, _n = loop.run(params, src)
            loss_v = (float(loop.last_aux)
                      if loop.last_aux is not None else float("nan"))
        else:
            for b in src:
                params, loss = jstep(params, convert(b))
                loss_v = float(loss)               # inline fetch sync (old path)
    dt = time.perf_counter() - t0

    if ckpt_policy is not None:
        ckpt_extra = {
            "ckpt_dir": ckpt_policy.dirname,
            "ckpt_every_steps": ckpt_policy.every_steps,
            "ckpt_saves": int(_mon_mod.default_registry()
                              .counter("ft.ckpt.saves").value - saves0),
            "resumed_step": loop.resumed_step,
        }

    _emit({
        "metric": "deepfm_ctr_hostfed_examples_per_sec_per_chip",
        "value": round(B * steps / dt, 1),
        "unit": "examples/s",
        "pipe": use_pipe,
        "step_variant": variant,
        "step_ms": round(dt / steps * 1e3, 2),
        "steps": steps,
        "batch": B,
        "loss": _finite(loss_v),
        **(ckpt_extra or {}),
        **_telemetry("deepfm_hostfed", steps, dt, B,
                     # a fresh copy: the timed loop donated `params`
                     compile_probe=lambda: jax.jit(step_fn).lower(
                         jax.tree.map(jnp.array, params0),
                         convert(mk_batch(-1)))),
    })


def bench_deepfm():
    """DeepFM CTR train-step throughput (BASELINE config 5).  vs_baseline is
    MEASURED sparse-path learning (AUC over trained ids, models/parity.py).

    Two lines: the headline scan-mode metric (device-side step loop, same
    measurement shape as the r05 runs, step variant autotuned per run — see
    _deepfm_step_variants), then the host-fed end-to-end line through the
    pipelined step engine (PADDLE_TPU_BENCH_PIPE=0 for the inline A/B)."""
    import jax
    import jax.numpy as jnp

    devs, on_tpu, peaks = _env()
    from paddle_tpu.models import deepfm

    if on_tpu:
        cfg = deepfm.DeepFMConfig()
        # long scan amortizes the per-dispatch host sync.  The
        # step is embedding-ROW-TRAFFIC-bound (profiled r5: ~19ms of the
        # ~30ms step was the [1M,10] table grad scatter, ~15M rows/s serial
        # TPU scatter; gathers another ~9ms) — the TPU analogue of the
        # reference's PS-network bottleneck for CTR.  The step variants
        # attack exactly that traffic; autotune below picks per run.
        B, iters, tune_iters = 8192, 200, 10
    else:
        cfg = deepfm.deepfm_tiny_config()
        B, iters, tune_iters = 64, 2, 2

    lr = 1e-3
    rng = np.random.RandomState(0)
    params = deepfm.init_deepfm_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "feat_ids": jnp.asarray(
            rng.randint(0, cfg.num_features, (B, cfg.num_fields)), jnp.int32),
        "label": jnp.asarray(rng.randint(0, 2, (B,)), jnp.float32),
    }
    def auc_parity():
        """BASELINE criterion: sparse lookup + SGD parity, measured by the
        shared recipe (models/parity.py): AUC over the trained ids of a
        synthetic learnable signal; 1.0 = the sparse path learns."""
        from paddle_tpu.models.parity import deepfm_synthetic_auc

        return "auc", deepfm_synthetic_auc()

    variants = _deepfm_step_variants(cfg, lr)
    variant, step_fn, timings = _autotune_deepfm_step(
        variants, params, batch, tune_iters)
    _run_sgd_bench("deepfm_ctr_examples_per_sec_per_chip", "examples/s",
                   lambda p, b: deepfm.deepfm_loss(p, b, cfg),
                   params, batch, iters, lr, B, B,
                   peaks=peaks, parity_fn=auc_parity, step_fn=step_fn,
                   extra={"step_variant": variant,
                          "autotune_step_ms": timings})

    _bench_deepfm_hostfed(cfg, params, step_fn, variant, B,
                          iters if on_tpu else 4, lr)


def bench_deepfm_hostps():
    """Opt-in (PADDLE_TPU_BENCH_HOSTPS=1) large-vocab DeepFM through the
    HostPS host-RAM sparse service (paddle_tpu/hostps): a vocab sized well
    past the HBM table budget lives in host RAM, hot ids are served from
    the HBM hot-row cache, pulls are double-buffered one batch ahead, and
    SelectedRows grads push back through the host-side applier.  Ids are
    zipf-distributed (CTR-shaped) so the cache earns its keep.  Reports
    examples/s + measured cache hit rate and pull/push latency; never runs
    by default, so the headline metrics are untouched."""
    import jax
    import jax.numpy as jnp

    devs, on_tpu, _peaks = _env()
    from paddle_tpu import profiler as prof
    from paddle_tpu.hostps import HostPSEmbedding, HostSGD, HostSparseTable
    from paddle_tpu.models import deepfm

    if on_tpu:
        # 200M x 11 f32 = 8.8 GiB: past the 60% table budget of a 16 GiB
        # chip, the honest beyond-HBM regime
        vocab, B, F, D, iters = 200_000_000, 4096, 39, 10, 30
        cache_slots = 1 << 18
    else:
        vocab, B, F, D, iters = 200_000, 256, 8, 8, 6
        cache_slots = 4096
    lr = 1e-3

    # one table of width D+1 carries embedding + first-order weight (one
    # pull instead of two)
    table = HostSparseTable(vocab, D + 1, optimizer=HostSGD(), seed=0,
                            name="deepfm_hostps")
    svc = HostPSEmbedding(table, cache_slots=cache_slots,
                          device=devs[0] if devs else None)

    # dense side: reuse the deepfm head with throwaway tiny tables
    cfg = deepfm.DeepFMConfig(num_features=2, num_fields=F, embed_dim=D,
                              mlp_dims=(64, 32) if not on_tpu else (400, 400))
    params = deepfm.init_deepfm_params(jax.random.PRNGKey(0), cfg)
    dense = {"mlp": params["mlp"], "bias": params["bias"]}

    rng = np.random.RandomState(0)

    def mk_ids():
        # zipf-hot head over the huge vocab, criteo-style
        z = rng.zipf(1.3, (B, F)).astype(np.int64)
        return (z * 2654435761) % vocab

    def mk_label(ids):
        return ((ids.sum(axis=1) % 2)).astype(np.float32)

    @jax.jit
    def step(values, inv, dense, label):
        def loss_fn(values, dense):
            v = values[inv]                       # [B, F, D+1]
            emb, lin = v[..., :D], v[..., D]
            p = dict(dense, w_linear=None, embed=None)
            logits = deepfm._deepfm_head(p, emb, lin)
            y = label.astype(jnp.float32)
            return jnp.mean(jnp.maximum(logits, 0) - logits * y
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        loss, (g_vals, g_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(values, dense)
        dense = jax.tree.map(lambda p, g: p - lr * g, dense, g_dense)
        return loss, g_vals, dense

    prof.reset_profiler()
    batches = [mk_ids() for _ in range(iters)]
    loss = float("nan")

    probe_args = []

    def run_one(ids, next_ids, dense):
        # consume this batch's (possibly prefetched) pull FIRST, then start
        # the next batch's prefetch so it overlaps the device step + push
        rows, values, inv = svc.pull_unique(ids)
        if next_ids is not None:
            svc.prefetch(next_ids)
        if not probe_args:
            # first batch's concrete step args double as the restart-cost
            # probe's lowering inputs (_telemetry compile_probe)
            probe_args.append((values, jnp.asarray(inv),
                               jnp.asarray(mk_label(ids))))
        loss, g_vals, dense = step(values, jnp.asarray(inv), dense,
                                   jnp.asarray(mk_label(ids)))
        svc.push(rows, np.asarray(g_vals[:rows.shape[0]]), lr)
        return float(loss), dense

    # warmup/compile + cache fill
    loss, dense = run_one(batches[0], None, dense)

    t0 = time.perf_counter()
    for k, ids in enumerate(batches):
        nxt = batches[k + 1] if k + 1 < len(batches) else None
        loss, dense = run_one(ids, nxt, dense)
    dt = time.perf_counter() - t0

    c = prof.counters()
    hits, misses = c.get("hostps.cache.hit", 0), c.get("hostps.cache.miss", 0)
    obs = prof.observations()
    _emit({
        "metric": "deepfm_hostps_examples_per_sec_per_chip",
        "value": round(B * iters / dt, 1),
        "unit": "examples/s",
        "cache_hit_rate": round(hits / max(hits + misses, 1), 4),
        "prefetch_hits": c.get("hostps.prefetch.hit", 0),
        "pull_ms_avg": round(obs["hostps.pull_ms"]["avg"], 3)
        if "hostps.pull_ms" in obs else None,
        "push_ms_avg": round(obs["hostps.push_ms"]["avg"], 3)
        if "hostps.push_ms" in obs else None,
        "vocab": vocab,
        "batch": B,
        "loss": _finite(loss),
        **_telemetry("deepfm_hostps", iters, dt, B,
                     compile_probe=lambda: step.lower(
                         probe_args[0][0], probe_args[0][1], dense,
                         probe_args[0][2])),
    })


def main():
    import argparse

    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--model",
                    choices=("all", "bert", "resnet50", "nmt", "deepfm",
                             "deepfm_hostps"),
                    default="all")
    args = ap.parse_args()
    from paddle_tpu import compile_cache

    compile_cache.place()
    if os.environ.get("PADDLE_TPU_BENCH_MONITOR"):
        # opt-in run telemetry: every config's JSON line gains a
        # "telemetry" block (per-step ms, compiles/recompiles, memory
        # watermark) and the timeline/metrics land in the monitor dir;
        # disable() at exit flushes the timeline and writes metrics.prom
        # even when a config died mid-run
        import atexit

        from paddle_tpu import monitor

        monitor.enable()
        atexit.register(monitor.disable)
    benches = {"bert": bench_bert, "resnet50": bench_resnet50,
               "nmt": bench_nmt, "deepfm": bench_deepfm,
               "deepfm_hostps": bench_deepfm_hostps}
    failed = []
    if args.model == "all":
        # every BASELINE config in one run (VERDICT r3 item 2); the
        # headline BERT metric prints LAST so the driver's single-line
        # parse still records it.  deepfm_hostps is strictly opt-in
        # (PADDLE_TPU_BENCH_HOSTPS=1) and slots before bert so it can
        # never displace the headline line.
        configs = ["resnet50", "nmt", "deepfm"]
        if os.environ.get("PADDLE_TPU_BENCH_HOSTPS"):
            configs.append("deepfm_hostps")
        configs.append("bert")
        for name in configs:
            try:
                benches[name]()
            except Exception as e:  # noqa: BLE001 — one config failing
                # shouldn't hide the rest; the run still exits non-zero
                import traceback

                traceback.print_exc()
                failed.append(name)
                print(json.dumps({"metric": name, "error": str(e)[:200],
                                  **_device_fields()}), flush=True)
    else:
        benches[args.model]()
    if failed:
        import sys

        print("bench: configs failed: %s" % ", ".join(failed),
              file=sys.stderr, flush=True)
        sys.exit(1)
    # opt-in perf-ledger follow-up: compare this run against the committed
    # BENCH trajectory (and gate under PADDLE_TPU_BENCH_LEDGER_CHECK=1)
    rc = _ledger_followup()
    if rc:
        import sys

        sys.exit(rc)


if __name__ == "__main__":
    main()
