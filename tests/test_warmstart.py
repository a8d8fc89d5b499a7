"""WarmStart persistent compile cache (paddle_tpu/warm.py + wiring).

Contract under test (ISSUE 13):

- the executable store round-trips compiled programs across Executor
  instances (process cache) and across PROCESSES (disk), bit-identically;
- cache-key SAFETY: a version-skewed header, a CRC-corrupt payload, a
  sentinel-flag or donation-flag drift each REFUSE the entry and fall back
  to a clean recompile — a poisoned cache can never load, wedge, or
  mis-execute;
- the recompile detector records a warm hit distinctly (cached="disk",
  never churn) yet still names a LATER key drift as a recompile;
- ExportedPredictor memoizes one compiled call per artifact + input
  signature (two predictors over the same artifact pay one compile);
- topology pre-compilation runs on a background thread after a committed
  checkpoint and lands post-shrink/post-grow entries in the store;
- trace_summary --check --max-resume-compile-secs gates the post-resume
  compile latency with a named evidence row.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import warm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_warm():
    warm.reset()
    yield
    warm.join_background(30)
    warm.reset()


def _store(tmp_path, keep=None):
    return warm.configure(str(tmp_path / "warmcache"), keep=keep)


def _build_program(width=16, seed=0):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, width, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    main.random_seed = seed
    return main, startup, loss


def _feed(n=4):
    rng = np.random.RandomState(7)
    return {"x": rng.rand(n, 8).astype("f4"),
            "y": rng.rand(n, 1).astype("f4")}


def _run_steps(exe, main, loss, steps=3):
    feed = _feed()
    out = None
    for _ in range(steps):
        out = exe.run(main, feed=feed, fetch_list=[loss.name])
    return np.asarray(out[0])


# -- fn-level store round trip ----------------------------------------------

def _warm_fn(i=0):
    import jax.numpy as jnp

    def fn(x):
        return jnp.tanh(x @ x.T).sum() + i

    return fn


def test_store_roundtrip_bitexact(tmp_path):
    _store(tmp_path)
    x = np.random.RandomState(0).rand(16, 16).astype("f4")
    a = warm.WarmCallable(_warm_fn(), {"k": "roundtrip"}, label="rt")
    r1 = np.asarray(a(x))
    assert a.last_source == "compiled"
    assert warm.store().entries()
    # a fresh callable over the same key+avals loads from disk
    b = warm.WarmCallable(_warm_fn(), {"k": "roundtrip"}, label="rt")
    r2 = np.asarray(b(x))
    assert b.last_source == "disk"
    assert b.deserialize_ms is not None
    np.testing.assert_array_equal(r1, r2)
    s = warm.stats()
    assert s["warm_hits"] == 1 and s["published"] >= 1


def test_store_refuses_version_skew(tmp_path, monkeypatch):
    st = _store(tmp_path)
    x = np.ones((8, 8), "f4")
    warm.WarmCallable(_warm_fn(), {"k": "ver"}, label="v")(x)
    warm.join_background(30)
    assert st.entries()
    # the next "process" runs a different jaxlib: the entry must REFUSE
    # (counted), fall back to a clean recompile and overwrite
    real = warm.version_fingerprint()
    monkeypatch.setattr(warm, "version_fingerprint",
                        lambda: dict(real, jaxlib="999.0.0"))
    c = warm.WarmCallable(_warm_fn(), {"k": "ver"}, label="v2")
    with pytest.warns(UserWarning, match="refused"):
        r = np.asarray(c(x))
    assert c.last_source == "compiled"
    assert np.isfinite(r).all()
    s = warm.stats()
    assert s["refused"] >= 1 and s["warm_misses"] >= 1
    # ...and the overwrite re-published under the NEW fingerprint: a
    # same-version lookup now hits
    warm.join_background(30)
    d = warm.WarmCallable(_warm_fn(), {"k": "ver"}, label="v3")
    d(x)
    assert d.last_source == "disk"


def test_store_refuses_crc_corruption(tmp_path):
    st = _store(tmp_path)
    x = np.ones((8, 8), "f4")
    ref = np.asarray(warm.WarmCallable(_warm_fn(), {"k": "crc"},
                                       label="c")(x))
    warm.join_background(30)
    (name,) = st.entries()
    path = os.path.join(st.dirname, name)
    with open(path, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        b = f.read(1)
        f.seek(-3, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    c = warm.WarmCallable(_warm_fn(), {"k": "crc"}, label="c2")
    with pytest.warns(UserWarning, match="refused"):
        r = np.asarray(c(x))
    assert c.last_source == "compiled"        # clean recompile, never load
    np.testing.assert_array_equal(r, ref)     # zero wrong numerics
    assert warm.stats()["refused"] >= 1


def test_donation_flag_drift_never_loads(tmp_path):
    """Same fn + avals, different donation config -> different key: the
    donating build must not adopt the non-donating entry (or vice versa)."""
    _store(tmp_path)
    x = np.ones((8, 8), "f4")
    a = warm.WarmCallable(_warm_fn(), {"k": "don"}, label="d0")
    a(x)
    warm.join_background(30)
    b = warm.WarmCallable(_warm_fn(), {"k": "don"},
                          jit_kwargs={"donate_argnums": (0,)}, label="d1")
    b(np.ones((8, 8), "f4"))
    assert b.last_source == "compiled"        # miss, not a cross-flag load
    assert warm.stats()["warm_misses"] >= 1


# -- executor wiring ---------------------------------------------------------

def test_fresh_executor_is_process_warm_hit():
    """Satellite: the compile cache is process-level — a fresh Executor
    re-running the same program pays ZERO compiles (and
    use_program_cache=False still compiles by request)."""
    main, startup, loss = _build_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    r1 = _run_steps(exe, main, loss)
    base = warm.stats()["compile_ms"]
    exe2 = fluid.Executor(fluid.CPUPlace())
    r2 = exe2.run(main, feed=_feed(), fetch_list=[loss.name])
    assert warm.stats()["compile_ms"] == base      # no compile paid
    assert np.isfinite(np.asarray(r2[0]))
    # cache disabled: compiles by request, does not poison the shared cache
    exe2.run(main, feed=_feed(), fetch_list=[loss.name],
             use_program_cache=False)
    assert warm.stats()["compile_ms"] > base


def test_executor_cross_instance_sentinel_drift_recompiles(tmp_path):
    """Sentinel-flag drift is a different key: flipping the sentinel on
    must compile a new entry, never adopt the sentinel-off executable."""
    from paddle_tpu import monitor

    _store(tmp_path)
    os.environ["PADDLE_TPU_WARM_SYNC_PUBLISH"] = "1"
    try:
        mon = monitor.enable(str(tmp_path / "mon"))
        main, startup, loss = _build_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _run_steps(exe, main, loss, steps=2)
        n_entries = len(warm.store().entries())
        assert n_entries >= 2                  # startup + main published
        from paddle_tpu.monitor import sentinel as sentinel_mod

        sentinel_mod.enable()
        base_hits = warm.stats()["warm_hits"]
        _run_steps(exe, main, loss, steps=1)
        # the sentinel variant is a MISS against the store (new key)...
        assert warm.stats()["warm_hits"] == base_hits
        # ...and publishes its own entry alongside the old one
        assert len(warm.store().entries()) > n_entries
    finally:
        os.environ.pop("PADDLE_TPU_WARM_SYNC_PUBLISH", None)
        monitor.disable()


def test_executor_disk_warm_hit_and_detector(tmp_path):
    """A fresh program object with IDENTICAL content warm-hits the disk
    store; the detector records it as cached="disk" (never churn) and a
    later feed-shape drift still names a recompile."""
    from paddle_tpu import monitor

    _store(tmp_path)
    os.environ["PADDLE_TPU_WARM_SYNC_PUBLISH"] = "1"
    try:
        mon = monitor.enable(str(tmp_path / "mon"))
        main, startup, loss = _build_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = _run_steps(exe, main, loss, steps=3)

        # same CONTENT, new objects — the in-process caches cannot help;
        # only the disk key (content fingerprint) can.  A respawned
        # process starts a fresh unique_name stream, so model rebuilds
        # land on the same var names; reproduce that here
        from paddle_tpu import unique_name

        unique_name.switch()
        main2, startup2, loss2 = _build_program()
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup2)
        hits0 = warm.stats()["warm_hits"]
        got = _run_steps(exe2, main2, loss2, steps=3)
        assert warm.stats()["warm_hits"] > hits0
        np.testing.assert_array_equal(ref, got)   # bit-identical math
        mon.timeline.flush()
        evs = monitor.read_events(
            str(tmp_path / "mon" / "timeline.jsonl"), ev="compile")
        disk = [e for e in evs if e.get("cached") == "disk"]
        assert disk and all(not e.get("recompile") for e in disk)
        assert any(e.get("deserialize_ms") is not None for e in disk)
        # drift AFTER the warm hit: a recompile, with the component named
        rec0 = mon.recompiles.total_recompiles
        exe2.run(main2, feed={"x": np.ones((9, 8), "f4"),
                              "y": np.ones((9, 1), "f4")},
                 fetch_list=[loss2.name])
        assert mon.recompiles.total_recompiles == rec0 + 1
        mon.timeline.flush()
        evs = monitor.read_events(
            str(tmp_path / "mon" / "timeline.jsonl"), ev="compile")
        assert any(e.get("recompile") and "feed" in e.get("diff", [])
                   for e in evs)
    finally:
        os.environ.pop("PADDLE_TPU_WARM_SYNC_PUBLISH", None)
        monitor.disable()


@pytest.mark.slow
def test_cross_process_warm_hit_roundtrip(tmp_path):
    """The acceptance shape: process A compiles+persists, process B (a
    fresh interpreter) warm-hits and reproduces the same numbers."""
    script = r"""
import json, os, sys
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import warm
warm.configure(sys.argv[1])
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", shape=[8], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, 16, act="relu")
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
    fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
rng = np.random.RandomState(7)
feed = {"x": rng.rand(4, 8).astype("f4"), "y": rng.rand(4, 1).astype("f4")}
out = None
for _ in range(3):
    out = exe.run(main, feed=feed, fetch_list=[loss.name])
warm.join_background(60)
print(json.dumps({"loss": float(np.asarray(out[0])),
                  "stats": warm.stats()}))
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PADDLE_TPU_WARM_SYNC_PUBLISH": "1"}
    env.pop("XLA_FLAGS", None)
    cache = str(tmp_path / "xproc")

    def run_once():
        r = subprocess.run([sys.executable, "-c", script, cache],
                           env=env, cwd=REPO, timeout=300,
                           capture_output=True, text=True)
        assert r.returncode == 0, (r.stdout, r.stderr)
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run_once()
    assert cold["stats"]["published"] >= 2     # startup + main
    assert cold["stats"]["warm_hits"] == 0
    hot = run_once()
    assert hot["stats"]["warm_hits"] >= 2
    assert hot["stats"]["compile_ms"] == 0     # nothing compiled warm
    assert hot["loss"] == cold["loss"]         # bit-identical


# -- predictor ---------------------------------------------------------------

def test_exported_predictor_single_compile_memo(tmp_path):
    """Satellite: two predictors over the same artifact pay ONE compile,
    and repeated same-shape calls never re-trace."""
    from paddle_tpu.inference import ExportedPredictor, export_inference_model

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6], dtype="float32")
        pred = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                                  main_program=main)
    export_inference_model(str(tmp_path), feed_shapes={"x": (4, 6)})

    xv = np.random.RandomState(0).rand(4, 6).astype("f4")
    base = warm.stats()["compile_ms"]
    p1 = ExportedPredictor(str(tmp_path))
    (o1,) = p1.run({"x": xv})
    after_first = warm.stats()["compile_ms"]
    assert after_first > base                  # the one compile
    p2 = ExportedPredictor(str(tmp_path))
    (o2,) = p2({"x": xv})                      # __call__ surface
    (o3,) = p1.run({"x": xv})
    assert warm.stats()["compile_ms"] == after_first   # memoized
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(o1, o3)
    # the compiled call persisted NEXT TO the artifact for replica spin-up
    assert os.path.isdir(os.path.join(str(tmp_path), ".warm"))


# -- pre-compilation ---------------------------------------------------------

def test_topology_precompiler_after_commit(tmp_path):
    """After a committed checkpoint, the background thread compiles the
    post-shrink/post-grow worlds' executables (rules-derived shapes) into
    the store — the elastic resize then restarts warm."""
    from paddle_tpu.ft import ckpt as fckpt
    from paddle_tpu.parallel.rules import hostps_row_range

    st = _store(tmp_path)
    vocab, dim = 64, 4

    def build_for_world(w):
        import jax.numpy as jnp

        lo, hi = hostps_row_range(0, w, vocab)

        def fn(rows):
            return jnp.tanh(rows).sum(axis=1)

        wc = warm.WarmCallable(
            fn, {"kind": "shard_apply", "world": w}, label="shard%d" % w)
        return wc, (jax.ShapeDtypeStruct((hi - lo, dim), np.float32),)

    warm.register_precompiler(
        warm.topology_precompiler(build_for_world, world=2))
    w = fckpt.save_train_state(str(tmp_path / "ck"), 1,
                               scope_state={"a": np.ones(3, "f4")},
                               hostps=[], asynchronous=False)
    w.finish()
    t = warm.precompile_thread()
    if t is not None:
        t.join(60)
    warm.join_background(60)
    assert warm.stats()["precompiled"] >= 1
    assert len(st.entries()) >= 2              # worlds 1 and 3
    # the post-shrink world's executable is already warm: ensure() hits
    wc, args = build_for_world(1)
    assert wc.ensure(*args) == "disk"


def _warm_step(loss_fn, opt, mesh, params, key, donate):
    """``make_train_step``'s program under the store, as a caller that names
    its model wraps it: ``warm.WarmCallable`` with the mesh, the specs, the
    donation flag and the loss's and optimizer's code in the key."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.train import TrainState, make_train_step

    specs = ({"w": P()}, {"w": ()}, {"x": P()})
    build = make_train_step(loss_fn, mesh, specs[0], specs[1], opt, specs[2],
                            donate=False)
    return warm.WarmCallable(
        build(TrainState.create(params, opt)),
        {"kind": "train_step", "key": key, "mesh": warm.mesh_desc(mesh),
         "specs": [repr(s) for s in specs],
         # an edited loss or optimizer must not be served the old math from
         # disk even when every shape/spec is unchanged
         "code": warm.code_fingerprint(loss_fn, opt[1]),
         "donate": bool(donate)},
        jit_kwargs={"donate_argnums": (0,) if donate else ()},
        label="train_step:%s" % key)


def test_warm_train_step_key(tmp_path):
    """A train step under ``warm.WarmCallable`` persists its executable and
    a rebuilt step over the same rules/mesh loads it, bit-identically."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.train import TrainState

    _store(tmp_path)
    os.environ["PADDLE_TPU_WARM_SYNC_PUBLISH"] = "1"
    try:
        mesh = make_mesh(1, 1, 1, devices=jax.devices()[:1])
        params = {"w": np.full((4, 4), 0.5, np.float32)}
        opt = (lambda p: {}, lambda g, o, p, lr: (
            {k: p[k] - lr * g[k] for k in p}, o))

        def loss_fn(p, b):
            return ((b["x"] @ p["w"]) ** 2).mean()

        def one(donate):
            step = _warm_step(loss_fn, opt, mesh, params, "ut_step", donate)
            st, loss = step(TrainState.create(params, opt),
                            {"x": np.ones((2, 4), np.float32)}, 0.1)
            return step, float(loss), np.asarray(st["params"]["w"])

        s1, l1, w1 = one(donate=False)
        assert s1.last_source == "compiled"
        warm.join_background(60)
        s2, l2, w2 = one(donate=False)
        assert s2.last_source == "disk"
        assert l1 == l2
        np.testing.assert_array_equal(w1, w2)
        # donation drift: its own key — never adopts the no-donate entry
        s3, l3, w3 = one(donate=True)
        assert s3.last_source in ("compiled", "disk")
        if s3.last_source == "disk":
            # a disk hit for a donating step must come from the donating
            # key's own (donation-free twin) entry, published separately
            assert l3 == l1
    finally:
        os.environ.pop("PADDLE_TPU_WARM_SYNC_PUBLISH", None)


# -- trace_summary gate ------------------------------------------------------

def test_trace_summary_resume_compile_gate(tmp_path):
    """--max-resume-compile-secs: tight budget fails a cold resume naming
    the evidence, passes a warm one; no resume at all fails."""
    def timeline(path, compiled_ms):
        evs = [{"ev": "monitor_start", "ts": 100.0, "pid": 1},
               {"ev": "resume", "ts": 101.0, "step": 3, "ckpt": "ckpt-3"},
               {"ev": "step", "ts": 102.0, "step": 4,
                "host_ms": compiled_ms, "compiled": True},
               {"ev": "step", "ts": 103.0, "step": 5, "host_ms": 2.0}]
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")

    cold = str(tmp_path / "cold" / "timeline.jsonl")
    warmt = str(tmp_path / "warm" / "timeline.jsonl")
    timeline(cold, 1800.0)
    timeline(warmt, 25.0)

    def check(path, budget):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "trace_summary.py"),
             "--check", "--max-resume-compile-secs", str(budget),
             "--timeline", path],
            capture_output=True, text=True, timeout=60, cwd=REPO)

    r = check(cold, 0.5)
    assert r.returncode == 2
    assert "first-step-after-resume" in r.stderr
    assert "resume compile [" in r.stdout
    r = check(warmt, 0.5)
    assert r.returncode == 0
    assert "resume compile [" in r.stdout
    # a run that never resumed cannot prove anything: fail, don't skip
    nores = str(tmp_path / "nores" / "timeline.jsonl")
    os.makedirs(os.path.dirname(nores), exist_ok=True)
    with open(nores, "w") as f:
        f.write(json.dumps({"ev": "step", "ts": 1.0, "step": 1,
                            "host_ms": 2.0}) + "\n")
    assert check(nores, 0.5).returncode == 2


def test_version_skew_refusal_leaves_entry_for_peers(tmp_path, monkeypatch):
    """Version skew is refused LOCALLY, never deleted: on a shared-fs
    store mid-rolling-upgrade the entry may be exactly right for the
    fleet members still on the other version."""
    st = _store(tmp_path)
    comp = jax.jit(lambda x: x + 1).lower(np.ones(3, "f4")).compile()
    key = {"k": "peer"}
    st.publish(key, comp)
    (name,) = st.entries()
    real = warm.version_fingerprint()
    monkeypatch.setattr(warm, "version_fingerprint",
                        lambda: dict(real, jaxlib="999.0.0"))
    with pytest.warns(UserWarning, match="version skew"):
        assert st.lookup(key) is None
    assert st.entries() == [name]          # still there for the peers
    monkeypatch.setattr(warm, "version_fingerprint", lambda: real)
    assert st.lookup(key) is not None      # and still valid for them


def test_train_step_code_drift_new_key(tmp_path):
    """Editing the loss math (same key, same shapes/specs) must not be
    served the OLD executable from disk."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.train import TrainState

    _store(tmp_path)
    os.environ["PADDLE_TPU_WARM_SYNC_PUBLISH"] = "1"
    try:
        mesh = make_mesh(1, 1, 1, devices=jax.devices()[:1])
        params = {"w": np.full((4, 4), 0.5, np.float32)}
        opt = (lambda p: {}, lambda g, o, p, lr: (
            {k: p[k] - lr * g[k] for k in p}, o))

        def run(loss_fn):
            step = _warm_step(loss_fn, opt, mesh, params, "code_drift",
                              False)
            _st, loss = step(TrainState.create(params, opt),
                             {"x": np.ones((2, 4), np.float32)}, 0.1)
            return step.last_source, float(loss)

        src1, l1 = run(lambda p, b: ((b["x"] @ p["w"]) ** 2).mean())
        assert src1 == "compiled"
        warm.join_background(60)
        # different MATH, identical key/spec/shapes: must compile fresh
        src2, l2 = run(lambda p, b: ((b["x"] @ p["w"]) ** 2).mean() * 3.0)
        assert src2 == "compiled"
        assert l2 == pytest.approx(3.0 * l1)
    finally:
        os.environ.pop("PADDLE_TPU_WARM_SYNC_PUBLISH", None)


def test_exported_predictor_per_dir_store(tmp_path):
    """The same artifact bytes deployed under a second model dir get their
    own beside-the-artifact .warm/ (a replica over EITHER dir stays warm)."""
    import shutil

    from paddle_tpu.inference import ExportedPredictor, export_inference_model

    src = tmp_path / "modelA"
    src.mkdir()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[5], dtype="float32")
        pred = fluid.layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(str(src), ["x"], [pred], exe,
                                  main_program=main)
    export_inference_model(str(src), feed_shapes={"x": (3, 5)})
    dst = tmp_path / "modelB"
    shutil.copytree(str(src), str(dst))

    xv = np.ones((3, 5), "f4")
    (oa,) = ExportedPredictor(str(src)).run({"x": xv})
    (ob,) = ExportedPredictor(str(dst)).run({"x": xv})
    np.testing.assert_array_equal(oa, ob)
    assert os.path.isdir(os.path.join(str(src), ".warm"))
    assert os.path.isdir(os.path.join(str(dst), ".warm"))
    assert os.listdir(os.path.join(str(dst), ".warm"))


def test_store_retention(tmp_path):
    st = _store(tmp_path, keep=3)
    x = np.ones((4, 4), "f4")
    for i in range(6):
        warm.WarmCallable(_warm_fn(i), {"k": "ret", "i": i},
                          label="r%d" % i)(x)
    warm.join_background(60)
    assert len(st.entries()) <= 3
