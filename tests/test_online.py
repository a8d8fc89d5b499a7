"""OnlineLoop: streaming train->serve with delta publish, zero-drop
hot-swap, and quarantine-gated rollback (paddle_tpu/online, ISSUE 16).

Contract: a StreamingSource feeds train_from_dataset forever and resumes
bit-exact from a committed cursor; a DeltaPublisher ships dense weights +
only the touched HostPS rows as an atomic, versioned publish chain that a
quarantined step can never enter; a VersionSwapper applies a chain to a
LIVE ServeEngine with zero dropped requests and zero recompiles.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, online
from paddle_tpu.dataset import DatasetFactory
from paddle_tpu.hostps.optimizer import HostAdagrad
from paddle_tpu.hostps.service import HostPSEmbedding
from paddle_tpu.hostps.table import HostSparseTable
from paddle_tpu.inference import export_inference_model, load_exported_model
from paddle_tpu.online import (DeltaPublisher, StreamingSource,
                               VersionSwapper, committed_publishes,
                               latest_version, load_chain_rows,
                               resolve_chain)
from paddle_tpu.parallel.checkpoint import save_checkpoint
from paddle_tpu.serving import BucketLattice, ServeEngine, ServeError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- fixtures --

def _write_ctr_file(path, rows, n_fields=4, vocab=60, seed=0):
    rng = np.random.RandomState(seed)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = rng.randint(0, vocab, n_fields)
            f.write("%d %s 1 %.1f\n"
                    % (n_fields, " ".join(map(str, ids)),
                       float(ids.sum() % 2)))
    return str(path)


def _make_dataset(files, batch=8, n_fields=4):
    ids = fluid.layers.data("feat_ids", shape=[n_fields], dtype="int64")
    label = fluid.layers.data("label", shape=[1], dtype="float32")
    ds = DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(batch)
    ds.set_thread(1)
    ds.set_filelist(list(files))
    ds.set_use_var([ids, label])
    return ds


def _rows_of(batches):
    return np.concatenate([b["feat_ids"] for b in batches])


# --------------------------------------------------------- StreamingSource --

def test_streaming_source_is_dataset_shaped_and_bounded(tmp_path):
    files = [_write_ctr_file(tmp_path / "a", 20, seed=1)]
    ds = _make_dataset(files)
    src = StreamingSource(ds)          # no provider: a bounded stream
    assert src.proto_desc is ds.proto_desc          # delegation
    assert src.queue_num is ds.queue_num
    batches = list(src._iter_batches())
    want = list(_make_dataset(files)._iter_batches(num_threads=1))
    np.testing.assert_array_equal(_rows_of(batches), _rows_of(want))
    wm = src.watermark
    assert wm["batches"] == len(batches) and wm["cursor"] is not None


def test_streaming_source_consumes_files_appearing_mid_stream(tmp_path):
    f0 = _write_ctr_file(tmp_path / "part-0", 16, seed=2)
    visible = [f0]
    src = StreamingSource(_make_dataset(list(visible)),
                          file_provider=lambda: list(visible),
                          poll_secs=0.01, idle_secs=5.0)
    got = []
    added = threading.Event()

    def producer():
        # only add the new file once the stream drained the first one —
        # the refresh-and-resume path, not the initial listing
        while src.watermark["batches"] < 2:
            time.sleep(0.005)
        visible.append(_write_ctr_file(tmp_path / "part-1", 16, seed=3))
        added.set()

    t = threading.Thread(target=producer)
    t.start()
    for cur, feed in src._iter_batches(with_cursor=True):
        got.append((cur, feed))
        if len(got) == 4:
            src.stop()
    t.join()
    assert added.is_set() and len(got) == 4
    # everything streams in file order, cursors strictly increase
    cursors = [c for c, _f in got]
    assert cursors == sorted(cursors) and cursors[-1][0] == 1
    ref = _make_dataset([f0, str(tmp_path / "part-1")])
    want = list(ref._iter_batches(num_threads=1))
    np.testing.assert_array_equal(
        _rows_of([f for _c, f in got]), _rows_of(want))


def test_streaming_source_resumes_bit_exact_from_cursor(tmp_path):
    files = [_write_ctr_file(tmp_path / ("p%d" % i), 20, seed=10 + i)
             for i in range(3)]
    full = list(StreamingSource(_make_dataset(files))._iter_batches(
        with_cursor=True))
    cut = len(full) // 2
    resume_from = full[cut - 1][0]
    # a fresh incarnation (new dataset object, same files) resumes
    # STRICTLY AFTER the committed cursor — no replay, no gap
    tail = list(StreamingSource(_make_dataset(files))._iter_batches(
        skip_to=resume_from, with_cursor=True))
    assert [c for c, _f in tail] == [c for c, _f in full[cut:]]
    np.testing.assert_array_equal(
        _rows_of([f for _c, f in tail]),
        _rows_of([f for _c, f in full[cut:]]))


def test_streaming_source_rejects_mutated_file_list(tmp_path):
    files = [_write_ctr_file(tmp_path / "x", 8, seed=4)]
    shuffled = [_write_ctr_file(tmp_path / "y", 8, seed=5)]
    src = StreamingSource(_make_dataset(files),
                          file_provider=lambda: list(shuffled))
    with pytest.raises(RuntimeError, match="append-only"):
        list(src._iter_batches())


def test_streaming_source_max_batches_and_idle_bound(tmp_path):
    files = [_write_ctr_file(tmp_path / "z", 64, seed=6)]
    src = StreamingSource(_make_dataset(files),
                          file_provider=lambda: list(files),
                          poll_secs=0.01, idle_secs=0.05, max_batches=3)
    assert len(list(src._iter_batches())) == 3
    # idle timeout ends the stream once the (static) provider goes dry
    src2 = StreamingSource(_make_dataset(files),
                           file_provider=lambda: list(files),
                           poll_secs=0.01, idle_secs=0.05)
    t0 = time.monotonic()
    n = len(list(src2._iter_batches()))
    assert n == 8 and time.monotonic() - t0 < 10


# ------------------------------------------------------- delta round-trip --

def _touch(table, rng, k=12):
    """One training interval: init some rows via pull, push grads."""
    ids = rng.randint(0, table.vocab_size, size=k).astype(np.int64)
    table.pull(ids)
    table.push(ids, rng.randn(k, table.dim).astype(np.float32), 0.1)
    return ids


def test_delta_chain_replays_bit_identical_to_full_snapshot(tmp_path):
    """Property-style: random touch patterns over N intervals; base + N-1
    deltas must replay (param AND moment slots) bit-identical to the live
    table's full snapshot."""
    rng = np.random.RandomState(0)
    for trial in range(3):
        pub_dir = str(tmp_path / ("chain%d" % trial))
        table = HostSparseTable(96, 6, seed=7, name="ctr",
                                optimizer=HostAdagrad())
        pub = DeltaPublisher(pub_dir, hostps=[table])
        state = {"w": rng.randn(4, 3).astype(np.float32)}
        for step in range(1, 5):
            _touch(table, rng, k=int(rng.randint(1, 20)))
            state["w"] = state["w"] + 1.0
            assert pub.publish(state, step=step) == step
        # deltas after the base are strictly the touched sets
        pubs = committed_publishes(pub_dir)
        assert [m["kind"] for _v, _p, m in pubs] == \
            ["base", "delta", "delta", "delta"]
        chain = resolve_chain(pub_dir)
        rows, arrays = load_chain_rows(chain, "ctr")
        ref_rows, ref_arrays, _meta = table.snapshot()
        np.testing.assert_array_equal(rows, ref_rows)
        for key in ref_arrays:
            np.testing.assert_array_equal(arrays[key], ref_arrays[key])
        # dense restores from the target publish alone
        dense = online.publish.load_chain_dense(
            chain, {"dense": {"w": np.zeros((4, 3), np.float32)}})
        np.testing.assert_array_equal(dense["dense"]["w"], state["w"])
        # ... and adopting into a FRESH serving table reproduces the bits
        serve = HostSparseTable(96, 6, seed=7, name="ctr",
                                optimizer=HostAdagrad())
        serve.adopt_rows(rows, arrays)
        s_rows, s_arrays, _m = serve.snapshot()
        np.testing.assert_array_equal(s_rows, ref_rows)
        np.testing.assert_array_equal(s_arrays["param"],
                                      ref_arrays["param"])


def test_delta_publish_failure_remarks_rows_for_next_publish(tmp_path):
    rng = np.random.RandomState(1)
    table = HostSparseTable(64, 4, seed=3, name="ctr")
    pub = DeltaPublisher(str(tmp_path / "chain"), hostps=[table])
    pub.publish({"w": np.zeros(2, np.float32)}, step=1)
    ids = _touch(table, rng)
    assert table.touched_rows_pending > 0
    # a publish that dies mid-write must hand the rows back
    from paddle_tpu.ft import chaos
    chaos.arm("ckpt_commit", at=1)
    try:
        with pytest.raises(chaos.ChaosError):
            pub.publish({"w": np.zeros(2, np.float32)}, step=2)
    finally:
        chaos.disarm()
    assert table.touched_rows_pending >= len(set(ids.tolist()))
    # corpse GC'd by a fresh incarnation; the retry re-ships the rows
    pub2 = DeltaPublisher(str(tmp_path / "chain"), hostps=[table])
    v = pub2.publish({"w": np.zeros(2, np.float32)}, step=2)
    assert v == 2 and table.touched_rows_pending == 0
    rows, _arrays = load_chain_rows(resolve_chain(str(tmp_path / "chain")),
                                    "ctr")
    assert set(ids.tolist()) <= set(rows.tolist())


def test_resharded_two_rank_publish_restores_on_one(tmp_path, monkeypatch):
    """A 2-rank saver fleet publishes one version (each rank its own row
    shard + dense shard); a 1-process serving replica replays it into a
    full-range table bit-exactly."""
    rng = np.random.RandomState(2)
    pub_dir = str(tmp_path / "chain")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.setenv("PADDLE_TPU_CKPT_BARRIER_SECS", "30")
    t0 = HostSparseTable(80, 4, seed=9, name="ctr", row_range=(0, 40))
    t1 = HostSparseTable(80, 4, seed=9, name="ctr", row_range=(40, 80))
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    pub1 = DeltaPublisher(pub_dir, hostps=[t1])
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    pub0 = DeltaPublisher(pub_dir, hostps=[t0])
    for t, lo, hi in ((t0, 0, 40), (t1, 40, 80)):
        ids = rng.randint(lo, hi, size=10).astype(np.int64)
        t.pull(ids)
        t.push(ids, rng.randn(10, 4).astype(np.float32), 0.1)
    dense = {"w": np.arange(6, dtype=np.float32)}
    # rank 1 publishes first (stages its shards, no COMMIT)...
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    pub1.publish(dense, step=3)
    assert latest_version(pub_dir) is None          # barrier not met yet
    # ...rank 0 sees both indexes at the barrier and COMMITs
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    assert pub0.publish(dense, step=3) == 1
    # the serving world is ONE process
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    chain = resolve_chain(pub_dir)
    assert chain[-1][2]["saver_world"] == 2
    rows, arrays = load_chain_rows(chain, "ctr")
    full = HostSparseTable(80, 4, seed=9, name="ctr")
    full.adopt_rows(rows, arrays)
    for t in (t0, t1):
        r, a, _m = t.snapshot()
        got = full.pull(r.reshape(-1, 1)).reshape(r.size, -1)
        np.testing.assert_array_equal(got, a["param"])
    got_dense = online.publish.load_chain_dense(
        chain, {"dense": {"w": np.zeros(6, np.float32)}})
    np.testing.assert_array_equal(got_dense["dense"]["w"], dense["w"])


# -------------------------------------------------------- quarantine gate --

def test_quarantined_step_never_enters_publish_chain(tmp_path):
    qdir = str(tmp_path / "quarantine")
    pub_dir = str(tmp_path / "chain")
    table = HostSparseTable(64, 4, seed=1, name="ctr")
    pub = DeltaPublisher(pub_dir, hostps=[table], quarantine_dir=qdir)
    state = {"w": np.zeros(3, np.float32)}
    assert pub.publish(state, step=3) == 1
    # the sentinel quarantines step 5 (its exact artifact shape/naming)
    save_checkpoint(qdir, {"poisoned": np.ones(2)}, step=5,
                    asynchronous=False, tag="quarantine")
    # the interval containing the diverged step is VETOED...
    assert pub.publish(state, step=6) is None
    assert latest_version(pub_dir) == 1
    # ...and the post-revert interval publishes normally
    assert pub.publish(state, step=9) == 2
    published_steps = [m["train_step"]
                       for _v, _p, m in committed_publishes(pub_dir)]
    assert published_steps == [3, 9]
    assert all(s != 5 and s != 6 for s in published_steps)


# ------------------------------------------------- engine swap regression --

FEED_SPEC = {"x": ((12,), "float32")}


def _artifact(dirname):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[12], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    exe.run(main, feed={"x": rng.rand(8, 12).astype("f4"),
                        "y": rng.rand(8, 1).astype("f4")},
            fetch_list=[loss])
    fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                  main_program=main)
    export_inference_model(dirname, feed_shapes={"x": (4, 12)},
                           poly_batch=True)
    return dirname


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    return _artifact(str(tmp_path_factory.mktemp("online_model")))


def test_swap_mid_trace_strands_no_futures_single_summary(
        artifact, tmp_path):
    """Satellite 1: a swap requested while a multi-step request is mid-
    trace completes it on the OLD weights, flips, serves the rest on the
    NEW ones — no dropped/failed futures, exactly one serve_summary."""
    out_dir = str(tmp_path / "mon")
    monitor.enable(out_dir)
    try:
        rng = np.random.RandomState(3)
        ep = load_exported_model(artifact)
        eng = ServeEngine(ep, BucketLattice([4, 8]), feed_spec=FEED_SPEC,
                          name="swap_t1")
        doubled = {n: v * 2.0 for n, v in ep._state.items()}
        with eng:
            big = eng.submit({"x": rng.rand(300, 12).astype("f4")})
            while eng.stats.registry.counter("swap_t1.admitted").value < 1:
                time.sleep(0.001)
            ev = eng.request_swap(lambda: ep.swap_state(doubled) and None,
                                  version=2, timeout=60)
            after = [eng.submit({"x": rng.rand(3, 12).astype("f4")})
                     for _ in range(4)]
            (big_out,) = big.result(timeout=60)
            outs = [f.result(timeout=60) for f in after]
        assert eng.version == 2 and ev["version"] == 2
        assert ev["stall_ms"] >= 0 and ev["apply_ms"] >= 0
        assert big_out.shape == (300, 1)
        # post-flip requests ran on the doubled weights
        ref = load_exported_model(artifact)
        ref.swap_state(doubled)
        for f, (got,) in zip(after, outs):
            (want,) = ref.run({"x": f.feed["x"]})
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        s = eng.last_summary
        assert s["completed"] == 5 and s["recompiles"] == 0
        assert s["new_compiled_sigs"] == 0
    finally:
        monitor.disable()
    events = monitor.read_events(os.path.join(out_dir, "timeline.jsonl"))
    summaries = [e for e in events if e.get("ev") == "serve_summary"
                 and e.get("ident", "").startswith("swap_t1")]
    flips = [e for e in events if e.get("ev") == "serve_flip"
             and e.get("ident", "").startswith("swap_t1")]
    assert len(summaries) == 1, "swap must not double-emit serve_summary"
    assert len(flips) == 1 and flips[0]["version"] == 2


def test_failed_swap_apply_keeps_old_version_serving(artifact):
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4]),
                      feed_spec=FEED_SPEC, name="swap_fail")

    def boom():
        raise RuntimeError("poisoned publish")

    with eng:
        with pytest.raises(RuntimeError, match="poisoned"):
            eng.request_swap(boom, version=9, timeout=60)
        assert eng.version is None and eng.error is None
        fut = eng.submit({"x": np.ones((2, 12), "f4")})
        fut.result(timeout=60)
    assert eng.last_summary["completed"] == 1


def test_swap_refused_when_not_serving_or_already_pending(artifact):
    eng = ServeEngine(load_exported_model(artifact), BucketLattice([4]),
                      feed_spec=FEED_SPEC, name="swap_refuse")
    with pytest.raises(ServeError, match="not serving"):
        eng.request_swap(lambda: None, version=1)
    results = []
    rng = np.random.RandomState(8)
    with eng:
        # a multi-step request holds the loop busy: the swap stays PENDING
        # (not yet applied) until the in-flight set drains.  15,000 steps,
        # about 0.8 s: 100 steps drain in 5 ms, before the swap thread has
        # run on a loaded host (6 xdist workers), and the swap applies at once
        big = eng.submit({"x": rng.rand(60000, 12).astype("f4")})
        while eng.stats.registry.counter("swap_refuse.admitted").value < 1:
            time.sleep(0.001)
        t = threading.Thread(target=lambda: results.append(
            eng.request_swap(lambda: None, version=1, timeout=60)))
        t.start()
        while eng._swap is None and not results:
            time.sleep(0.001)
        assert eng._swap is not None
        with pytest.raises(ServeError, match="already pending"):
            eng.request_swap(lambda: None, version=2)
        big.result(timeout=60)
        t.join()
    assert results and results[0]["version"] == 1
    # the engine stays one-shot after swaps
    with pytest.raises(ServeError, match="one-shot"):
        eng.start()


def test_swap_state_refuses_signature_change(artifact):
    ep = load_exported_model(artifact)
    good = {n: v + 1.0 for n, v in ep._state.items()}
    assert ep.swap_state(good) == len(good)
    name = next(iter(good))
    with pytest.raises(ValueError, match="signature"):
        ep.swap_state({**good, name: np.zeros((1, 1), np.float32)})
    with pytest.raises(KeyError, match="missing"):
        ep.swap_state({})


# ------------------------------------------- swapper end-to-end (in-proc) --

def test_version_swapper_chain_flip_and_rollback(artifact, tmp_path):
    """The tentpole, in one process: publish base + delta from a training
    table, flip a LIVE engine to each under load, zero recompiles, then
    roll back."""
    rng = np.random.RandomState(5)
    pub_dir = str(tmp_path / "chain")
    train_table = HostSparseTable(64, 4, seed=11, name="serve_ctr")
    pub = DeltaPublisher(pub_dir, hostps=[train_table])

    ep = load_exported_model(artifact)
    serve_table = HostSparseTable(64, 4, seed=11, name="serve_ctr")
    emb = HostPSEmbedding(serve_table, cache_slots=16, read_only=True)
    eng = ServeEngine(ep, BucketLattice([4, 8]), feed_spec=FEED_SPEC,
                      name="swap_e2e")
    swapper = VersionSwapper(eng, ep, pub_dir, hostps=[emb])

    ids1 = _touch(train_table, rng)
    v1_state = {n: v * 1.5 for n, v in ep._state.items()}
    assert pub.publish(v1_state, step=2, train_wall=time.time()) == 1
    with eng:
        ev1 = swapper.apply(1)
        assert ev1["kind"] == "base" and ev1["chain_len"] == 1
        assert ev1["freshness_lag_s"] >= 0
        # the preverify saw only warm sources — never a fresh compile
        assert ev1["preverified"].get("compiled", 0) == 0
        # the serving table now holds the TRAINED rows verbatim
        r, a, _m = train_table.snapshot()
        np.testing.assert_array_equal(
            serve_table.pull(r.reshape(-1, 1)).reshape(r.size, -1),
            a["param"])
        for n in v1_state:
            np.testing.assert_array_equal(ep._state[n], v1_state[n])
        # next interval: push more rows, publish a delta, poll picks it up
        _touch(train_table, rng)
        v2_state = {n: v * 2.0 for n, v in v1_state.items()}
        assert pub.publish(v2_state, step=4, train_wall=time.time()) == 2
        ev2 = swapper.poll()
        assert ev2["version"] == 2 and ev2["kind"] == "delta"
        assert swapper.poll() is None          # already fresh
        r, a, _m = train_table.snapshot()
        np.testing.assert_array_equal(
            serve_table.pull(r.reshape(-1, 1)).reshape(r.size, -1),
            a["param"])
        # requests keep completing across all of it
        futs = [eng.submit({"x": rng.rand(3, 12).astype("f4")})
                for _ in range(4)]
        for f in futs:
            f.result(timeout=60)
        # rollback re-applies v1 through the same flip path
        ev_rb = swapper.rollback()
        assert ev_rb["version"] == 1 and ev_rb["rollback"]
        assert swapper.version == 1
        for n in v1_state:
            np.testing.assert_array_equal(ep._state[n], v1_state[n])
    s = eng.last_summary
    assert s["recompiles"] == 0 and s["new_compiled_sigs"] == 0
    assert s["completed"] == 4
    assert eng.stats.registry.counter("swap_e2e.swaps").value == 3
    del ids1


def test_swapper_refuses_unknown_version(artifact, tmp_path):
    ep = load_exported_model(artifact)
    eng = ServeEngine(ep, BucketLattice([4]), feed_spec=FEED_SPEC,
                      name="swap_none")
    swapper = VersionSwapper(eng, ep, str(tmp_path / "nochain"))
    with pytest.raises(ValueError, match="no committed publish chain"):
        swapper.apply(3)
    assert swapper.poll() is None          # empty chain: nothing to do


# ----------------------------------------------------- chain housekeeping --

def test_publish_chain_prune_keeps_newest_bases(tmp_path):
    pub_dir = str(tmp_path / "chain")
    state = {"w": np.zeros(2, np.float32)}
    versions = []
    for i in range(3):                     # 3 incarnations = 3 chains
        pub = DeltaPublisher(pub_dir, keep_bases=2)
        versions.append(pub.publish(state, step=10 * i + 1))
        versions.append(pub.publish(state, step=10 * i + 2))
    pubs = committed_publishes(pub_dir)
    kinds = [m["kind"] for _v, _p, m in pubs]
    # the oldest chain (base+delta) was pruned; two newest remain
    assert kinds == ["base", "delta", "base", "delta"]
    assert [v for v, _p, _m in pubs] == versions[2:]
    chain = resolve_chain(pub_dir)
    assert [v for v, _p, _m in chain] == versions[4:]


def test_resolve_chain_rejects_gaps(tmp_path):
    import shutil

    pub_dir = str(tmp_path / "chain")
    pub = DeltaPublisher(pub_dir, keep_bases=5)
    state = {"w": np.zeros(2, np.float32)}
    for s in (1, 2, 3):
        pub.publish(state, step=s)
    shutil.rmtree(os.path.join(pub_dir, "publish-2"))
    with pytest.raises(RuntimeError, match="gap"):
        resolve_chain(pub_dir)
    manifest = json.load(open(os.path.join(
        pub_dir, "publish-3", online.publish.MANIFEST)))
    assert manifest["kind"] == "delta" and manifest["base_version"] == 1
