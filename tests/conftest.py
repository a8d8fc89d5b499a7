"""Test fixtures: run everything on a simulated 8-device CPU mesh
(SURVEY.md §4 — multi-device tests use XLA's host-platform device simulation
instead of the reference's subprocess-NCCL localhost harness where possible;
loss-parity subprocess tests spawn their own workers)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the helper modules' asserts explain themselves as a test file's do
pytest.register_assert_rewrite("decoder_reference", "tpu_compile")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP.md): register the marker so
    # the multi-GiB hostps stress test and friends deselect cleanly
    config.addinivalue_line(
        "markers",
        "slow: multi-GiB / long-running stress tests, excluded from tier-1")


_exit_status = [0]


def pytest_sessionfinish(session, exitstatus):
    _exit_status[0] = int(exitstatus)


def pytest_unconfigure(config):
    # After a full tier-1 run the interpreter spends ~20s in shutdown —
    # GC'ing thousands of jax executables/arrays plus the XLA client's
    # atexit teardown — with the verdict already printed.  That dead time
    # eats straight into the suite's CI wall budget, so flush and leave.
    # (unconfigure runs after the terminal summary; the exit code is the
    # one pytest would have returned.)
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_exit_status[0])


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs / scope / name generator."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, scope, unique_name

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_gen = unique_name.switch()
    old_scope = scope._global_scope
    scope._global_scope = scope.Scope()
    from paddle_tpu import clip as _clip

    old_clip = _clip._global_clip
    _clip._global_clip = None
    yield
    _clip._global_clip = old_clip
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    scope._global_scope = old_scope
