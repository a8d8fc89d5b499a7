"""Where the LM head's device time goes, by instruction, and how many rows
it computed: one cell of the benchmark through the program's normal path.

    chiprun -- python3 scripts/lm_head_profile.py bert_base.s512_scan 7 [--ones]
    chiprun -- python3 scripts/lm_head_profile.py olmoe_1b_7b.s4096_scan 7 \
        --scope moe --scope router --top 80

``--scope`` lists another scope's instructions (``monitor.devscope``'s
vocabulary, several allowed; an instruction belongs to its innermost scope,
as the benchmark's shares count it), and the step's busiest instructions
are printed with their ``op_name`` whatever their scope.

Builds the cell's trainer and stages its batches as the scan driver does,
runs one dispatch under a monitor session (the scan driver opens none, so
this is where ``monitor.train.lm_head_rows_share`` and, for a decoder, its
readings of the batch and the weights — ``monitor.train.moe_*``,
``router_*``, ``retention_*``, ``mamba*``, ``attn_gate_*``, ``exit_*`` —
and every ``monitor.kernels.*_calls`` count of what the trace did are read
on the chip; what the configuration fixes is no gauge: the flash kernels'
grid is printed from ``flash_attention.packed_grid`` at the cell's shape),
traces one more, and joins the trace with THIS process's scope map
(``monitor.devscope``; a map compiled elsewhere need not number its
instructions the same way).  ``--ones`` replaces the mask by all ones, the
causal-LM shape no cell sends.  The scope's milliseconds under the head's
forward and under its backward rule are printed apart (since PR 74 the
head makes a block's logits once and its gradient in the forward rule: the
backward rule's line, a multiply, is the receipt that the second pass is
gone).  The head's partition of the vocabulary
(``transformer._vocab_chunks`` of the cell's head matrix) is printed beside
its milliseconds; ``--cut 9728,9728,9728,8800`` replaces it for this
process's compile (chunk rows that sum to the vocabulary: the script's
experiment, the program has no such option).  Needs a TPU; prints no
device number otherwise.
"""

import argparse
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("--ones", action="store_true")
    ap.add_argument("--scope", action="append")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--cut", type=lambda s: [int(r) for r in s.split(",")])
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("lm_head_profile: no TPU, nothing to measure", file=sys.stderr)
        return 2

    from benchmark.drivers import train_scan
    from benchmark.harness import build, manifest as mf, trace_reduce, tracing
    from benchmark.harness.cellrun import Ctx
    from benchmark.harness.spans import Spans
    from paddle_tpu import compile_cache, monitor
    from paddle_tpu.kernels.flash_attention import packed_grid
    from paddle_tpu.monitor import devscope
    from paddle_tpu.parallel import transformer

    compile_cache.place()
    m = mf.load(ROOT)
    cell = mf.cell(m, args.cell)
    ctx = Ctx()
    ctx.config = mf.read_json(ROOT, mf.config_entry(m, cell["config"])["file"])
    ctx.traffic = mf.read_json(ROOT, "benchmark", "traffic",
                               args.cell + ".json")
    ctx.seed, ctx.spans = args.seed, Spans()
    ctx.dims = build.cell_dims(ctx.config, ctx.traffic)
    lr = float(ctx.config["lr"])
    ctx.trainer = tr = build.build_trainer(
        ctx.config, ctx.traffic, args.seed, jax.devices()[:cell["chips"]])
    params = tr.state["params"]
    emb = params["lm_head"] if "lm_head" in params else params["tok_emb"]
    if args.cut:
        if sum(args.cut) != emb.shape[0] or min(args.cut) < 1:
            ap.error("--cut has to sum to the vocabulary, %d rows"
                     % emb.shape[0])
        offsets = np.cumsum([0] + args.cut[:-1]).tolist()
        transformer._vocab_chunks = lambda emb: list(zip(offsets, args.cut))
    print("head: %s %s, %s partition of the vocabulary (offset, rows): %s"
          % (emb.dtype, emb.shape, "--cut's" if args.cut else "the head's",
             transformer._vocab_chunks(emb)))
    staged = train_scan._stage(ctx)
    if args.ones:
        staged["mask"] = jnp.ones_like(staged["mask"])
    steps = int(ctx.traffic["staged_batches"])

    with tempfile.TemporaryDirectory() as tmp:
        mon = monitor.enable(os.path.join(tmp, "monitor"), flight=False)
        rows0 = mon.registry.counter("monitor.train.lm_head_rows").value
        np.asarray(tr.run_steps(staged, lr))            # compiles or loads
        print("rows: monitor.train.lm_head_rows_share %s, "
              "monitor.train.lm_head_rows %d for %d steps"
              % (mon.registry.gauge("monitor.train.lm_head_rows_share").value,
                 mon.registry.counter("monitor.train.lm_head_rows").value
                 - rows0, steps))
        ids, cfg = staged["ids"], tr.cfg
        heads, seq = cfg.n_heads // cfg.tp, ids.shape[-1]
        blocks = transformer._packed_flash_blocks(
            cfg, heads, seq, cfg.kv_heads // cfg.tp)
        if cfg.attn_mode == "heads" and blocks:
            print("flash: %s (batch row, head-block) pairs a grid step, %s "
                  "grid steps a layer and pass (flash_attention.packed_grid)"
                  % packed_grid(
                      ids.shape[-2] // tr.mesh.shape["dp"], seq, heads,
                      cfg.head_dim, *blocks, itemsize=cfg.jdtype.itemsize,
                      n_kv_heads=cfg.kv_heads // cfg.tp, causal=cfg.causal))
        for row in mon.registry.snapshot():    # a decoder's own
            if row["name"].startswith(("monitor.train.moe_",
                                       "monitor.train.router_",
                                       "monitor.train.retention_",
                                       "monitor.train.mamba",
                                       "monitor.train.attn_gate_",
                                       "monitor.train.exit_",
                                       "monitor.kernels.")):
                print("monitor: %s%s %s" % (
                    row["name"], row["labels"] or "", row.get("value")))
        monitor.disable()
        np.asarray(tr.run_steps(staged, lr))
        tracing._start(os.path.join(tmp, "trace"), 0)
        losses = np.asarray(tr.run_steps(staged, lr))
        jax.profiler.stop_trace()
        dev = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(os.path.join(tmp, "trace")))).devices[0]

    names = next(iter(devscope.scope_maps().values()))
    scopes = args.scope or ["lm_head"]
    rows, busiest, totals = [], [], {}
    for name, ns in dev["by_name"].items():
        op = names.get(name, "")
        key = devscope.classify(op) if op else ("unmapped", None)
        totals[key] = totals.get(key, 0.0) + ns
        busiest.append((ns / steps / 1e6, name, key[0], op[-130:]))
        if key[1] in scopes:
            rows.append((ns / steps / 1e6, name, key[0], re.sub(
                r".*?(%s)\)*/" % "|".join(scopes), "", op)[-100:]))
    print("%s, %s: device busy %.3f ms a step, last loss %.6g"
          % (args.cell, "mask of ones" if args.ones else "the cell's mask",
             dev["busy_ns"] / steps / 1e6, losses[-1]))
    stats = jax.devices()[0].memory_stats()
    print("memory: peak_bytes_in_use %d + peak_bytes_reserved %d"
          % (stats["peak_bytes_in_use"], stats.get("peak_bytes_reserved", 0)))
    for key, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:14]:
        print("  %-10s %-12s %8.3f ms a step" % (key + (ns / steps / 1e6,)))
    # a custom_vjp's forward rule is traced under jvp(, its backward rule
    # under transpose(: the head does its work in the first
    print("lm_head: forward rule %.3f ms a step, backward rule %.3f ms a step"
          % tuple(totals.get((phase, "lm_head"), 0.0) / steps / 1e6
                  for phase in ("forward", "backward")))
    for title, found, top in (
            ("the step's busiest instructions", busiest, 12),
            ("instructions under " + ", ".join(scopes), rows, args.top)):
        print("%s (%d, %.3f ms a step):"
              % (title, len(found), sum(r[0] for r in found)))
        for ms, name, phase, op in sorted(found, reverse=True)[:top]:
            print("  %7.3f ms  %-42s %-9s %s" % (ms, name, phase, op))
    return 0


if __name__ == "__main__":
    sys.exit(main())
