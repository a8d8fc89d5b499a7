"""PR 28's one per-layer metric: ``flash_short_roofline`` is
``flash_roofline``'s reader under a name of its own, for the one cell whose
whole sequence is one short block; it stands at the end of ``per_layer``
(an entry anywhere else reads to the driver as a change to what was there),
and no entry the benchmark had took the cell."""

import os

import pytest

from benchmark.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "bert_base.s128_scan"


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_the_entry_stands_last_and_lists_the_one_cell(manifest):
    last = manifest["per_layer"][-1]
    assert last == {"name": "flash_short_roofline", "unit": "%",
                    "better": "higher", "source": "device_trace",
                    "layer": "kernels", "moves": "train_throughput",
                    "workloads": [CELL]}
    for e in manifest["per_layer"][:-1]:
        assert CELL not in e.get("workloads", ())
    assert "flash_short_roofline" in {
        e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}


def test_its_reader_is_flash_roofline_s():
    short = mf.module("layer_metrics", "flash_short_roofline")
    assert short.read is mf.module("layer_metrics", "flash_roofline").read
    # on a run with no trace, or a program with no flash kernel, it reads
    # nothing and does not raise
    assert short.read(None, None, {}, {"peaks": None}) is None
