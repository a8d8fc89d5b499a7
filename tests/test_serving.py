"""ServeLoop: continuous-batching serving on the pre-compiled lattice
(paddle_tpu/serving + the strict recompile gate + read-only HostPS +
MemScope admission + the serve_bench CI gate).

Contract (ISSUE 15): requests pad to a pre-declared bucket lattice whose
every point is AOT-compiled at start (steady state never recompiles — the
strict detector raises), a fast request never stalls behind a slow one,
sparse CTR lookups never write the table, and admission backpressures
instead of OOMing.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, serving
from paddle_tpu.inference import export_inference_model, load_exported_model
from paddle_tpu.monitor.recompile import RecompileDetector, RecompileStorm
from paddle_tpu.monitor.registry import StatRegistry
from paddle_tpu.serving import (Backpressure, BucketLattice, CTRLookup,
                                RequestTooLarge, ServeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- fixtures --

def _train_and_export(dirname, poly_axes=None, with_seq=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if with_seq:
            # per-position (elementwise) model: padding along the seq axis
            # is bit-exact by construction
            x = fluid.layers.data("x", shape=[8], dtype="float32")
            pred = fluid.layers.scale(x, scale=2.5)
        else:
            x = fluid.layers.data("x", shape=[12], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=16, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if not with_seq:
        rng = np.random.RandomState(0)
        for _ in range(2):
            exe.run(main, feed={"x": rng.rand(16, 12).astype("f4"),
                                "y": rng.rand(16, 1).astype("f4")},
                    fetch_list=[loss])
    fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                  main_program=main)
    if with_seq:
        export_inference_model(dirname, feed_shapes={"x": (2, 8)},
                               poly_axes=poly_axes
                               or {"x": {0: "b", 1: "l"}})
    else:
        export_inference_model(dirname, feed_shapes={"x": (4, 12)},
                               poly_batch=True)
    return dirname


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    return _train_and_export(
        str(tmp_path_factory.mktemp("serve_model")))


@pytest.fixture(scope="module")
def seq_artifact(tmp_path_factory):
    return _train_and_export(
        str(tmp_path_factory.mktemp("serve_seq")), with_seq=True)


FEED_SPEC = {"x": ((12,), "float32")}


# ---------------------------------------------------------------- lattice --

def test_bucket_lattice_routing():
    lat = BucketLattice([4, 8, 16], seq_buckets=[8, 32])
    assert lat.route(3, 5) == (4, 8)
    assert lat.route(16, 32) == (16, 32)
    assert lat.route(9, 9) == (16, 32)
    assert len(lat) == 6 and (8, 32) in lat.points()
    with pytest.raises(RequestTooLarge):
        lat.route(17, 8)
    with pytest.raises(RequestTooLarge):
        lat.route(4, 33)
    with pytest.raises(ValueError):
        BucketLattice([8, 4])            # not ascending
    with pytest.raises(ValueError):
        lat.route(3)                     # seq declared, none given
    # batch-only lattice has no seq leg
    assert BucketLattice([2, 4]).route(3) == (4, None)


# ------------------------------------------------- strict recompile gate --

def test_recompile_detector_strict_raises_and_names_component():
    reg = StatRegistry()
    det = RecompileDetector(reg, warn_after=0, strict=True)
    det.record_warm("prog", {"feed": "a"})        # serving baseline
    with pytest.raises(RecompileStorm) as ei:
        det.record_compile("prog", {"feed": "b"})
    assert ei.value.ident == "prog" and "feed" in ei.value.diff
    # the evidence landed BEFORE the raise
    assert reg.counter("monitor.recompile").value == 1
    assert det.recompiles() == 1


def test_recompile_detector_strict_trips_every_offense_after_budget():
    reg = StatRegistry()
    det = RecompileDetector(reg, warn_after=2, strict=True)
    det.record_compile("p", {"feed": 1})          # first compile: free
    det.record_compile("p", {"feed": 2})          # 1st recompile: budgeted
    for i in range(3, 5):            # 2nd+ recompile: EVERY one raises
        with pytest.raises(RecompileStorm):
            det.record_compile("p", {"feed": i})
    # non-strict keeps the historic warn-once behavior
    det2 = RecompileDetector(StatRegistry(), warn_after=1)
    det2.record_compile("p", {"feed": 1})
    with pytest.warns(UserWarning, match="recompiled"):
        det2.record_compile("p", {"feed": 2})     # 1st recompile: warns
    det2.record_compile("p", {"feed": 3})         # warned once, not again


# -------------------------------------------- predictor bucket pad/slice --

def test_exported_predictor_pads_to_bucket_bit_exact(artifact):
    rng = np.random.RandomState(1)
    ep = load_exported_model(artifact)
    ep.declare_batch_buckets([4, 8])
    xb = rng.rand(4, 12).astype("f4")
    (full,) = ep.run({"x": xb})                   # exact bucket
    (padded,) = ep.run({"x": xb[:3]})             # 3 -> padded to 4
    # same bucket, pad rows zeros: the real rows are BIT-exact
    assert np.array_equal(padded, full[:3])
    assert padded.shape == (3, 1)
    # n=2 and n=3 share the bucket-4 signature: ONE compiled entry
    ep.run({"x": xb[:2]})
    assert len(ep._fast) == 1
    with pytest.raises(ValueError, match="largest declared bucket"):
        ep.run({"x": rng.rand(9, 12).astype("f4")})


def test_exported_predictor_ensure_compiled_sources(artifact):
    ep = load_exported_model(artifact)
    src1, compiled = ep.ensure_compiled({"x": ((8, 12), "float32")})
    assert src1 in ("compiled", "disk") and compiled is not None
    src2, _ = ep.ensure_compiled({"x": ((8, 12), "float32")})
    assert src2 == "cached"


# ------------------------------------------------------ continuous engine --

def test_engine_continuous_mixed_sizes_correct(artifact):
    rng = np.random.RandomState(2)
    ref = load_exported_model(artifact)
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4, 8]),
                      feed_spec=FEED_SPEC, name="serve_t1")
    with eng:
        sizes = [3, 1, 20, 2, 8, 5]
        reqs = [(rng.rand(s, 12).astype("f4"),) for s in sizes]
        futs = [eng.submit({"x": x}) for (x,) in reqs]
        outs = [fut.result(timeout=60) for fut in futs]
    s = eng.last_summary
    # reference runs AFTER the engine summary: ref shares the artifact's
    # process-wide WarmCallable, so its exact-shape compiles would
    # otherwise inflate new_compiled_sigs
    for (x,), (got,) in zip(reqs, outs):
        (want,) = ref.run({"x": x})
        assert got.shape == want.shape
        # different buckets may differ in the final ulp (per-shape XLA
        # codegen); within-bucket padding bit-exactness is asserted in
        # test_exported_predictor_pads_to_bucket_bit_exact
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert s["completed"] == len(sizes)
    assert s["admitted"] == s["evicted"] == len(sizes)
    assert s["recompiles"] == 0
    # the belt under the detector: ZERO signatures compiled after the
    # lattice pre-compile — steady state never met XLA
    assert s["new_compiled_sigs"] == 0
    assert s["points"] == 2
    assert s["rows"] == sum(sizes)


def test_engine_seq_buckets_pad_bit_exact(seq_artifact):
    rng = np.random.RandomState(3)
    ref = load_exported_model(seq_artifact)
    lat = BucketLattice([2, 4], seq_buckets=[4, 8])
    eng = ServeEngine(load_exported_model(seq_artifact), lat,
                      feed_spec={"x": ((serving.engine.SEQ,), "float32")},
                      name="serve_seq")
    with eng:
        cases = [rng.rand(3, 3).astype("f4"), rng.rand(1, 8).astype("f4"),
                 rng.rand(6, 5).astype("f4")]
        futs = [eng.submit({"x": x}, seq_len=x.shape[1]) for x in cases]
        for x, fut in zip(cases, futs):
            (got,) = fut.result(timeout=60)
            (want,) = ref.run({"x": x})
            assert got.shape[0] == x.shape[0]
            # outputs come back at the REQUEST'S OWN seq bucket (even when
            # co-batched with a longer request at a wider step bucket);
            # the real positions are bit-exact for the per-position model
            assert got.shape[1] == lat.route_seq(x.shape[1])
            np.testing.assert_array_equal(got[:, :x.shape[1]], want)
        with pytest.raises(RequestTooLarge):
            eng.submit({"x": rng.rand(2, 9).astype("f4")}, seq_len=9)
    assert eng.last_summary["recompiles"] == 0


def test_queue_admit_evict_ordering_slow_producer(artifact):
    """A slow producer trickles requests in while the engine serves: every
    request completes, same-size requests complete in submit order, and
    the admit/evict counters balance."""
    rng = np.random.RandomState(4)
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4, 8]),
                      feed_spec=FEED_SPEC, name="serve_slowprod")
    futs = []

    def producer():
        for _i in range(8):
            futs.append(eng.submit({"x": rng.rand(2, 12).astype("f4")}))
            time.sleep(0.02)

    with eng:
        t = threading.Thread(target=producer)
        t.start()
        t.join()
        done = [f.result(timeout=60) and f for f in futs]
    ends = [f.t_done for f in futs]
    assert all(e is not None for e in ends)
    # FIFO completion for a uniform trickle (each fits one step)
    assert ends == sorted(ends)
    s = eng.last_summary
    assert s["completed"] == 8 and s["admitted"] == 8 and s["evicted"] == 8
    assert s["backpressure"] == 0 and s["recompiles"] == 0


def test_small_request_not_stalled_behind_large(artifact):
    """THE continuous-batching property: a 1-row request submitted right
    after a 64-row one completes BEFORE it in continuous mode, after it in
    static mode."""
    rng = np.random.RandomState(5)
    big = rng.rand(400, 12).astype("f4")      # ~50 steps at bucket 8
    small = rng.rand(1, 12).astype("f4")
    order = {}
    for mode in ("static", "continuous"):
        eng = ServeEngine(load_exported_model(artifact),
                          BucketLattice([4, 8]), feed_spec=FEED_SPEC,
                          mode=mode, name="serve_hol_%s" % mode)
        with eng:
            fb = eng.submit({"x": big})
            # submit the small request once the big one is ADMITTED (not
            # merely queued) so "behind the giant" is a fact, not a race
            admitted = eng.stats.registry.counter(
                "serve_hol_%s.admitted" % mode)
            deadline = time.monotonic() + 10
            while admitted.value < 1 and time.monotonic() < deadline:
                time.sleep(0.001)
            fs = eng.submit({"x": small})
            fb.result(timeout=60)
            fs.result(timeout=60)
        order[mode] = (fb.t_done, fs.t_done)
    b_end, s_end = order["static"]
    assert s_end > b_end, "static must be head-of-line blocked"
    b_end, s_end = order["continuous"]
    assert s_end < b_end, "continuous must evict the small request early"


# ------------------------------------------------------ read-only HostPS --

def test_read_only_cache_mode_never_writes(artifact):
    from paddle_tpu.hostps.service import HostPSEmbedding
    from paddle_tpu.hostps.table import HostSparseTable

    rng = np.random.RandomState(6)
    table = HostSparseTable(128, 4, seed=11, name="ro_table")
    emb = HostPSEmbedding(table, cache_slots=16, read_only=True)
    ids = rng.randint(0, 128, size=(5, 3)).astype(np.int64)
    v1 = np.asarray(emb.pull(ids))
    # value parity with a materializing table built from the same seed
    want = HostSparseTable(128, 4, seed=11).pull(ids)
    np.testing.assert_array_equal(v1, want)
    # ... and the serving table is byte-for-byte untouched
    assert table.rows_initialized == 0
    assert not table._live.any()
    assert not table._param.any()
    for a in table._slots.values():
        assert not a.any()
    # second pull: HBM cache hits serve the same bits
    hits_before = emb.cache.hits
    v2 = np.asarray(emb.pull(ids))
    np.testing.assert_array_equal(v1, v2)
    assert emb.cache.hits > hits_before
    assert table.rows_initialized == 0
    # every push surface refuses
    with pytest.raises(RuntimeError, match="read-only"):
        emb.push(np.array([1]), np.ones((1, 4), np.float32), 0.1)
    with pytest.raises(RuntimeError, match="read-only"):
        emb.push_in_jit(np.array([1]), np.ones((1, 4), np.float32), 0.1)
    # CTRLookup demands the read-only contract
    with pytest.raises(ValueError, match="read-only"):
        CTRLookup(HostPSEmbedding(HostSparseTable(8, 2)), "ids")
    lk = CTRLookup(emb, "ids", out_name="emb")
    out = lk({"ids": ids[:2]})
    assert out["emb"].shape == (2, 12) and "ids" not in out


# --------------------------------------------------- MemScope admission --

def test_admission_backpressure_under_tight_memscope_limit(
        artifact, monkeypatch):
    from paddle_tpu.monitor import memscope

    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4]),
                      feed_spec=FEED_SPEC, name="serve_bp")
    with eng:
        # a limit far below one lattice-point batch: admission must refuse
        # (Backpressure), NOT enqueue toward an OOM
        monkeypatch.setenv("PADDLE_TPU_MEMSCOPE_LIMIT", "64")
        assert eng._need_bytes and eng._need_bytes > 64
        with pytest.raises(Backpressure):
            eng.submit({"x": np.zeros((2, 12), "f4")})
        assert eng.stats.registry.counter("serve_bp.backpressure").value == 1
        # headroom restored (and the 0.25s verdict TTL expired): serving
        # resumes — backpressure is a state, not a death
        monkeypatch.delenv("PADDLE_TPU_MEMSCOPE_LIMIT")
        time.sleep(0.3)
        fut = eng.submit({"x": np.ones((2, 12), "f4")})
        fut.result(timeout=60)
    memscope.reset()


# ------------------------------------------------- strict gate, end-to-end --

def test_engine_off_lattice_dispatch_trips_strict_gate(artifact):
    """A shape outside the pre-compiled set must RAISE (RecompileStorm)
    and fail the pending futures — never silently compile under load."""
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4, 8]),
                      feed_spec=FEED_SPEC, name="serve_trip")
    with eng:
        # sabotage: pretend bucket 8 was never pre-compiled
        eng._precompiled.discard((8, None))
        fut = eng.submit({"x": np.zeros((8, 12), "f4")})
        with pytest.raises(RecompileStorm):
            fut.result(timeout=60)
        assert isinstance(eng.error, RecompileStorm)
        with pytest.raises(serving.ServeError, match="died"):
            eng.submit({"x": np.zeros((1, 12), "f4")})


def test_engine_rejects_malformed_request_without_dying(artifact):
    """A request with the wrong feed names is a per-request ValueError at
    submit — the loop (and every other client) keeps serving."""
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4]),
                      feed_spec=FEED_SPEC, name="serve_malformed")
    with eng:
        with pytest.raises(ValueError, match="contract"):
            eng.submit({"wrong_name": np.zeros((2, 12), "f4")})
        with pytest.raises(ValueError, match="contract"):
            eng.submit({"x": np.zeros((2, 12), "f4"),
                        "extra": np.zeros((2, 3), "f4")})
        fut = eng.submit({"x": np.ones((2, 12), "f4")})
        fut.result(timeout=60)
    assert eng.error is None and eng.last_summary["completed"] == 1


def test_engine_stop_fails_leftover_requests(artifact):
    """stop(drain=False) must fail queued requests, never strand them."""
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4]),
                      feed_spec=FEED_SPEC, name="serve_leftover")
    eng.start()
    futs = [eng.submit({"x": np.ones((2, 12), "f4")}) for _ in range(4)]
    eng.stop(drain=False)
    for f in futs:
        try:
            f.result(timeout=10)    # served before the stop landed, or...
        except serving.ServeError:
            pass                    # ...failed loudly — never a hang
        assert f.done()
    # engines are one-shot: a restart must refuse loudly, not spawn a
    # loop that exits instantly while submits keep failing
    with pytest.raises(serving.ServeError, match="one-shot"):
        eng.start()


def test_stats_summary_is_per_engine_despite_shared_prefix(artifact):
    """Two engines sharing one name (in-process restart / A-B) must each
    report their OWN counts: registry counters are cumulative, summaries
    are deltas."""
    for i in (1, 2):
        eng = ServeEngine(load_exported_model(artifact),
                          BucketLattice([4]), feed_spec=FEED_SPEC,
                          name="serve_shared")
        with eng:
            for _ in range(i):      # 1 request, then 2
                eng.submit({"x": np.ones((2, 12), "f4")}).result(timeout=60)
        assert eng.last_summary["admitted"] == i
        assert eng.last_summary["evicted"] == i


# ----------------------------------------------------- monitor surfacing --

def test_trace_summary_serve_section(artifact, tmp_path):
    out_dir = str(tmp_path / "mon")
    monitor.enable(out_dir)
    try:
        eng = ServeEngine(load_exported_model(artifact),
                          BucketLattice([4, 8]), feed_spec=FEED_SPEC,
                          name="serve_ts")
        rng = np.random.RandomState(7)
        with eng:
            futs = [eng.submit({"x": rng.rand(s, 12).astype("f4")})
                    for s in (1, 6, 3)]
            for f in futs:
                f.result(timeout=60)
    finally:
        monitor.disable()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_summary.py"),
         "--timeline", out_dir, "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    summary = json.loads(r.stdout.splitlines()[-1])
    sv = summary.get("serve")
    assert sv and sv["steps"] >= 1 and sv["recompiles"] == 0
    assert sv["modes"]["continuous"]["completed"] == 3
    assert sv["modes"]["continuous"]["p99_ms"] is not None
    assert sv["engines"]["continuous"]["points"] == 2


# ------------------------------------------------------------ perf ledger --

def _serve_snap(path, p50, p99, qps):
    tail = "\n".join(json.dumps(
        {"metric": m, "serve": True, "p50_ms": p50, "p99_ms": p99,
         "qps": qps}) for m in ("serve_static", "serve_continuous"))
    with open(path, "w") as f:
        json.dump({"cmd": "serve_bench", "rc": 0, "tail": tail}, f)


def test_perf_ledger_learns_serve_trajectory(tmp_path):
    hist = str(tmp_path / "hist")
    os.makedirs(hist)
    # a BENCH trajectory beside the SERVE one: the families gate apart
    for n, tok in (("BENCH_r01.json", 100000.0), ("BENCH_r02.json", 101000.0)):
        with open(os.path.join(hist, n), "w") as f:
            json.dump({"rc": 0, "tail": json.dumps(
                {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
                 "value": tok, "mfu": 0.5})}, f)
    ledger = os.path.join(REPO, "scripts", "perf_ledger.py")

    def run(extra=()):
        return subprocess.run(
            [sys.executable, ledger, "--history-dir", hist, "--check"]
            + list(extra), capture_output=True, text=True, timeout=60)

    # improving trajectory: PASS
    _serve_snap(os.path.join(hist, "SERVE_r01.json"), 50.0, 800.0, 100.0)
    _serve_snap(os.path.join(hist, "SERVE_r02.json"), 45.0, 700.0, 120.0)
    r = run()
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "serve snapshots" in r.stdout
    # p99 rise beyond the serve tolerance: FAIL naming metric + field
    _serve_snap(os.path.join(hist, "SERVE_r03.json"), 50.0, 1300.0, 110.0)
    r = run()
    assert r.returncode == 2
    assert "field=p99_ms" in r.stderr and "rise" in r.stderr
    os.remove(os.path.join(hist, "SERVE_r03.json"))
    # qps collapse: FAIL the higher-is-better direction
    _serve_snap(os.path.join(hist, "SERVE_r03.json"), 50.0, 700.0, 40.0)
    r = run()
    assert r.returncode == 2 and "field=qps" in r.stderr
    # a tolerant budget passes the same history
    r = run(["--serve-tolerance", "0.9"])
    assert r.returncode == 0, (r.stdout, r.stderr)


def test_perf_ledger_committed_history_green():
    """The committed SERVE_r01 history gates green — the exact CI
    invocation."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_ledger.py"),
         "--check"], capture_output=True, text=True, timeout=60, cwd=REPO)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "PASS" in r.stdout and "serve snapshots" in r.stdout


# ------------------------------------------------------- serve_bench gate --

def _run_bench(extra, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)      # the bench owns its own device count
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--check"] + extra, env=env, cwd=REPO, timeout=timeout,
        capture_output=True, text=True)


def test_serve_bench_smoke_gate():
    """Tier-1 (ISSUE 15 acceptance): tiny lattice, mixed request sizes —
    zero steady-state recompiles, continuous beats static on p99, QPS
    holds, read-only table untouched."""
    r = _run_bench(["--smoke"], timeout=420)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "serve_bench: PASS" in r.stdout
    assert "0 recompiles" in r.stdout


@pytest.mark.slow
def test_serve_bench_full_gate():
    """The full mixed-size drill (the SERVE_r*.json configuration)."""
    r = _run_bench([], timeout=560)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "serve_bench: PASS" in r.stdout


# ------------------------------------------------- FleetServe (in-process) --

def test_replica_info_fit_waste():
    """The routing score's first key: padding rows the replica's lattice
    wastes on the request's first step."""
    from paddle_tpu.serving import ReplicaInfo

    info = ReplicaInfo(0)
    info.batch_buckets, info.max_batch = (2, 4, 8), 8
    assert [info.fit_waste(r) for r in (1, 2, 3, 4, 5, 8)] \
        == [1, 0, 1, 0, 3, 0]
    # >= max_batch spans steps — waste 0, any replica fits it equally
    assert info.fit_waste(9) == 0
    # identity not yet known (hello pending): every fit is neutral
    blank = ReplicaInfo(1)
    assert blank.fit_waste(5) == 0


def _router_with(tmp_path, idents):
    """A FleetRouter over ``{rid: (buckets, load)}`` with no wire I/O —
    the hot path under test is pure bookkeeping by design."""
    from paddle_tpu.serving import FleetRouter

    router = FleetRouter(str(tmp_path), replicas=sorted(idents),
                         registry=StatRegistry())
    for rid, (buckets, load) in idents.items():
        info = router._replicas[rid]
        info.batch_buckets = tuple(buckets)
        info.max_batch = max(buckets)
        info.depth = int(load)
    return router


def test_router_pick_prefers_fit_then_load_then_round_robin(tmp_path):
    # fit first: rows=2 wastes 0 on r1's lattice, 6 on r0's — load loses
    router = _router_with(tmp_path, {0: ((8,), 0), 1: ((2, 4, 8), 5)})
    picked = router._pick(2)
    assert picked.rid == 1
    router._note_reply(picked, {"depth": 5})
    # equal fit: least load wins
    router = _router_with(tmp_path, {0: ((2, 4), 3), 1: ((2, 4), 1)})
    assert router._pick(2).rid == 1
    # equal fit and load: the round-robin cursor rotates the tie
    router = _router_with(tmp_path, {0: ((4,), 0), 1: ((4,), 0)})
    seen = set()
    for _ in range(4):
        picked = router._pick(4)
        seen.add(picked.rid)
        router._note_reply(picked, {"depth": 0})    # release the charge
    assert seen == {0, 1}


def test_router_pick_skips_suspects_until_cooloff(tmp_path):
    router = _router_with(tmp_path, {0: ((4,), 0), 1: ((4,), 9)})
    router._replicas[0].suspect_until = time.monotonic() + 60
    assert router._pick(4).rid == 1       # the idle replica is suspect
    # everyone suspect or excluded -> None (the submit loop breathes)
    router._replicas[1].suspect_until = time.monotonic() + 60
    assert router._pick(4) is None
    router._replicas[0].suspect_until = 0.0
    assert router._pick(4).rid == 0       # cool-off expiry readmits
    assert router._pick(4, exclude={0, 1}) is None


def test_router_note_reply_folds_piggybacked_load(tmp_path):
    router = _router_with(tmp_path, {0: ((4,), 0)})
    info = router._pick(4)
    assert info.outstanding == 1          # _pick charges the dispatch
    router._note_reply(info, {"depth": 7, "inflight": 3, "version": 9})
    assert (info.outstanding, info.depth, info.inflight, info.version,
            info.served) == (0, 7, 3, 9, 1)
    # a failed attempt only releases the charge — no stale fold-in
    info2 = router._pick(4)
    router._note_reply(info2, None, ok=False)
    assert info.outstanding == 0 and info.served == 1


def test_autoscale_signal_both_directions():
    from paddle_tpu.serving import autoscale_signal

    reg = StatRegistry()

    def snap(loads, suspect=()):
        return {i: {"depth": d, "outstanding": 0,
                    "suspect": i in suspect}
                for i, d in enumerate(loads)}

    d, why, ml = autoscale_signal(snap([6, 6, 6]), high_load=4.0,
                                  registry=reg)
    assert (d, why, ml) == (4, "queue_depth", 6.0)
    d, why, _ = autoscale_signal(snap([0, 0, 0]), low_load=0.25,
                                 min_replicas=1, registry=reg)
    assert (d, why) == (2, "idle")
    # bounds clamp both directions
    d, _, _ = autoscale_signal(snap([9, 9]), high_load=1.0,
                               max_replicas=2, registry=reg)
    assert d == 2
    d, why, _ = autoscale_signal(snap([0]), min_replicas=1, registry=reg)
    assert (d, why) == (1, "steady")
    # memory headroom gone -> scale up even when queues look fine
    d, why, _ = autoscale_signal(snap([1, 1]), hbm_frac=0.95,
                                 high_load=4.0, registry=reg)
    assert (d, why) == (3, "memory_headroom")
    # a suspect replica is excluded from mean load, desired holds n
    d, why, _ = autoscale_signal(snap([0, 8], suspect={0}),
                                 high_load=9.0, low_load=0.0,
                                 registry=reg)
    assert d == 2
    # a partial outage must NEVER read as "idle": the mean is over the
    # alive set only, so mostly-suspect fleets measure ~0 load — scaling
    # down then would retire a healthy replica mid-outage
    d, why, _ = autoscale_signal(snap([0, 0, 0], suspect={0, 1}),
                                 low_load=0.25, min_replicas=1,
                                 registry=reg)
    assert (d, why) == (3, "replacing_suspects")


def test_router_respawn_adoption_resets_control_seq(tmp_path):
    """A respawned replica starts an empty seq-dedup table expecting
    seq 1 — adoption (the ShardRestartedError path) must reseed the
    router's control counter from the fresh server's hello, or every
    post-respawn swap/retire dies on a 'seq gap' refusal and a rolling
    deploy aborts mid-fleet."""
    from paddle_tpu.hostps import wire as ps_wire
    from paddle_tpu.serving import FleetRouter

    wire = str(tmp_path)

    def make_handler(box, tag):
        def handler(op, payload, client):
            if op == "hello":
                return {"batch_buckets": [4], "max_batch": 4,
                        "pid": os.getpid(), "version": tag,
                        "last_seq": box[0].last_seq(client)}
            if op == "submit":
                return {"outputs": [tag], "depth": 0, "inflight": 0,
                        "version": tag}
            if op == "swap":
                return {"replica": 0, "version": payload["version"]}
            raise ValueError(op)
        return handler

    box = [None]
    box[0] = srv = ps_wire.WireServer(wire, 0, make_handler(box, "g1"),
                                      workers=4, poll=0.005)
    srv.start()
    srv.mark_ready()
    router = FleetRouter(wire, replicas=[0], registry=StatRegistry(),
                         deadline=5.0, poll=0.005).connect(timeout=10.0)
    info = router._replicas[0]
    # one pre-crash control op consumes seq 1 on generation 1
    router._control(info, "swap", {"version": "v2"})
    assert (info.next_seq, srv.last_seq(router.wire.client_id)) == (2, 1)
    srv.stop()

    # respawn: new generation, EMPTY dedup table
    box2 = [None]
    box2[0] = srv2 = ps_wire.WireServer(wire, 0, make_handler(box2, "g2"),
                                        workers=4, poll=0.005)
    srv2.start()
    srv2.mark_ready()
    try:
        # the data-plane submit trips ShardRestartedError -> the router
        # adopts (commit_generation + re-hello) and re-issues
        out = router.submit({"x": np.zeros((2, 3), np.float32)},
                            timeout=20.0)
        assert out == ["g2"]
        assert info.next_seq == 1, "seq floor not reseeded on adoption"
        # the post-respawn control op is ACCEPTED, not seq-gap refused
        res = router._control(info, "swap", {"version": "v3"})
        assert res["version"] == "v3"
        assert srv2.last_seq(router.wire.client_id) == 1
    finally:
        srv2.stop()


def test_apply_autoscale_spawns_past_adopted_replicas(tmp_path):
    """Scale-up over a fleet the manager did NOT spawn (procs empty,
    router serving rids 0..2) must pick a FRESH rid — reusing rid 0
    would pass wait_ready on the live replica's READY file and leave
    two engines draining one wire inbox."""
    from paddle_tpu.serving import FleetManager

    mgr = FleetManager(str(tmp_path), "artifact", str(tmp_path),
                       feeds=["x:4:float32"])

    class AdoptedRouter:
        added = None

        def replica_ids(self):
            return [0, 1, 2]

        def add_replica(self, rid):
            self.added = rid

    spawned = []
    mgr.spawn = lambda rid: spawned.append(rid)
    mgr.wait_ready = lambda rids: None
    router = AdoptedRouter()
    action, rid = mgr.apply_autoscale(router, desired=4)
    assert (action, rid) == ("spawn", 3)
    assert spawned == [3] and router.added == 3


def test_fleet_parse_feed_triples():
    from paddle_tpu.serving.fleet import _parse_feed

    assert _parse_feed(["x:12:float32", "tok:seq:int32",
                        "img:4,4:float32"]) \
        == {"x": ((12,), "float32"), "tok": (("seq",), "int32"),
            "img": ((4, 4), "float32")}


# ---------------------------------------------------------------------------
# LoadShield primitives (serving/shield.py) + router integration
# ---------------------------------------------------------------------------


def test_retry_budget_earn_spend_refund():
    from paddle_tpu.serving.shield import RetryBudget

    b = RetryBudget(ratio=0.5, cap=2.0, seed=1.0)
    assert b.tokens == 1.0
    assert b.try_spend()                  # the seed covers one re-route
    assert not b.try_spend()              # dry: counted denial, no retry
    assert (b.spent, b.denied) == (1, 1)
    for _ in range(10):
        b.observe()                       # primaries earn, capped at cap
    assert b.tokens == 2.0
    assert b.try_spend() and b.try_spend() and not b.try_spend()
    b.refund()                            # a hedge that never dispatched
    assert b.tokens == 1.0 and b.spent == 2
    snap = b.snapshot()
    assert snap["denied"] == 2 and snap["ratio"] == 0.5


def test_replica_breaker_trip_cooloff_probe_cycle():
    from paddle_tpu.serving.shield import ReplicaBreaker

    br = ReplicaBreaker(trip_ms=100.0, cooloff_s=2.0, min_samples=3)
    now = 1000.0
    for _ in range(4):
        br.record(10.0, False, now)       # healthy: stays closed
    assert br.state == br.CLOSED and br.admit(now) is True
    for _ in range(8):
        br.record(400.0, False, now)      # degraded-NOT-dead: EWMA climbs
    assert br.state == br.OPEN and br.trips == 1
    assert br.admit(now + 1.0) is False           # cooling off: hold
    assert br.admit(now + 2.5) == "probe"         # cooloff elapsed
    assert br.admit(now + 2.6) == "probe"         # still owed a verdict
    br.record(12.0, False, now + 3.0)             # good probe closes...
    assert br.state == br.CLOSED
    assert br.lat_ms == 12.0 and br.n == 1        # ...and resets the stats
    for _ in range(8):
        br.record(400.0, False, now + 4.0)        # re-trip
    assert br.admit(now + 7.0) == "probe"
    br.record(400.0, False, now + 7.1)            # bad probe re-opens
    assert br.state == br.OPEN and br.trips == 2


def test_shed_policy_priority_scaling():
    from paddle_tpu.serving.shield import ShedPolicy

    assert ShedPolicy().verdict(0, 1e9) is None   # inert default
    p = ShedPolicy(watermark=2.0, retry_after_ms=75.0)
    # low sheds at 1x, normal at 2x, high at 4x the watermark
    assert p.verdict(0, 2.5) == 75.0
    assert p.verdict(1, 2.5) is None
    assert p.verdict(1, 4.5) == 75.0
    assert p.verdict(2, 4.5) is None
    assert p.verdict(2, 8.5) == 75.0
    assert p.sheds == 3
    # out-of-range priorities clamp instead of raising
    assert p.verdict(-3, 1.5) is None and p.verdict(99, 7.0) is None


def test_shield_config_inert_defaults(tmp_path):
    """The inert default must cost nothing: no breaker object at all on
    the replicas (make_breaker -> None), shed gate unarmed."""
    from paddle_tpu.serving.shield import ShieldConfig

    cfg = ShieldConfig()
    assert cfg.make_breaker() is None
    assert cfg.make_shed().watermark is None
    armed = ShieldConfig(breaker_trip_ms=150.0)
    assert armed.make_breaker() is not None
    router = _router_with(tmp_path, {0: ((4,), 0), 1: ((4,), 0)})
    assert not router._shed_armed
    assert all(info.breaker is None
               for info in router._replicas.values())


def test_router_submit_sheds_typed_when_armed(tmp_path):
    from paddle_tpu.serving import FleetRouter
    from paddle_tpu.serving.queue import Shed

    router = FleetRouter(str(tmp_path), replicas=[0],
                         registry=StatRegistry(),
                         shield={"watermark": 2.0, "retry_after_ms": 40.0})
    assert router._shed_armed
    info = router._replicas[0]
    info.batch_buckets, info.max_batch = (4,), 4
    info.depth = 5
    router._rebuild_order()               # depth set by hand: recount
    with pytest.raises(Shed) as exc:
        router.submit({"x": np.zeros((2, 4), np.float32)}, priority=0)
    assert exc.value.retry_after_ms == 40.0
    assert router.shield_snapshot()["sheds"] == 1
    # high priority rides a 4x watermark: the same load is admitted
    # (it fails later on wire I/O against a non-replica — no Shed)
    assert router.shed.verdict(2, router._mean_load()) is None


def test_router_load_sum_tracks_every_mutation(tmp_path):
    """_mean_load is lock-free off the running _load_sum — it must agree
    with a recount after picks, releases, and piggybacked depth folds."""
    router = _router_with(tmp_path, {0: ((4,), 0), 1: ((4,), 0)})

    def recount():
        return sum(i.outstanding + i.depth
                   for i in router._replicas.values())

    a = router._pick(4)
    b = router._pick(4)
    assert router._load_sum == recount() == 2
    router._note_reply(a, {"depth": 7})   # release + depth fold
    assert router._load_sum == recount() == 8
    router._note_reply(b, None, ok=False)  # failed attempt: release only
    assert router._load_sum == recount() == 7
    c = router._pick(4)
    router._unpick(c)                     # undone dispatch
    assert router._load_sum == recount() == 7
    assert router._mean_load() == 3.5
