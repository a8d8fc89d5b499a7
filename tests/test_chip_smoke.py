"""chip_smoke.py refuses to run without a TPU; its phase functions are
rehearsed here at tiny sizes on the CPU.  Plus the two helpers the chip path
leans on: the compile-cache placement and the kernels' platform probe."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_chip_smoke_fails_without_a_tpu():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert "platform=cpu" in res.stdout          # names what it found
    assert "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout              # and prints no result


def test_chip_smoke_phases_rehearsed_tiny(tmp_path):
    import chip_smoke
    from paddle_tpu.models import bert

    # the train phase's LAST check is for the Mosaic kernels in the step.
    # On the CPU they interpret, so the rehearsal must get exactly that far
    # (losses fell, second call compiled nothing) and then refuse: interpret
    # mode cannot pass for the chip.
    with pytest.raises(AssertionError, match="WITHOUT Mosaic kernels"):
        chip_smoke.train_phase(jax.devices(), bert.bert_tiny_config(),
                               batch=4, seq=32)
    head = dict(num_fields=4, embed_dim=2)
    prog = chip_smoke.program_phase(str(tmp_path), mlp_dims=(8, 8), batch=32,
                                    **head)
    assert prog["artifact"] == str(tmp_path / "artifact")
    sv = chip_smoke.serve_phase(prog["artifact"], buckets=(2, 4),
                                sizes=(3, 1, 6), **head)
    assert sv["completed"] == 3 and sv["recompiles"] == 0
    assert sv["warm"]["refused"] == 0 and sv["warm"]["poisoned"] == 0


def test_compile_cache_placement(monkeypatch, tmp_path):
    from paddle_tpu import compile_cache

    before = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    # on the CPU nothing is set in code, whether the environment names a
    # directory or not
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place() == str(tmp_path)
    assert updates == []
    # unset, on the CPU: no cache (XLA:CPU executables reload broken)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.place() is None and updates == []
    # unset, on a chip: one absolute path, whatever the working directory,
    # and a key that holds the programs' metadata (named scopes, lines): a
    # profile never shows another commit's names
    import types

    chip = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    metadata = ("jax_compilation_cache_include_metadata_in_key", True)
    paths = []
    for cwd in (str(tmp_path), REPO):
        monkeypatch.chdir(cwd)
        paths.append(compile_cache.place())
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")
    assert ("jax_compilation_cache_dir", paths[0]) in updates
    assert metadata in updates
    # set from outside, on a chip: the directory is the environment's, the
    # key holds the metadata all the same
    del updates[:]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place() == str(tmp_path)
    assert updates == [metadata]
    assert jax.config.jax_compilation_cache_dir == before   # tests stay cold


def test_on_tpu_propagates_a_failed_device_probe(monkeypatch):
    from paddle_tpu.kernels import _common

    assert _common.on_tpu() is False            # positively cpu here

    def boom(*_a):
        raise RuntimeError("backend unavailable")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="backend unavailable"):
        _common.on_tpu()
