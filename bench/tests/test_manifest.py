"""Every cell and metric of ``BENCHMARK.json`` finds its files by name,
and every metric's reader returns nothing where a run holds nothing for
it to read."""
import json
import os

import pytest

from bench import run
from bench.tests.conftest import PEAK, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files(cell):
    manifest, c, mc, traffic, limits = run.load_cell(cell)
    gen, driver = run.modules(traffic)
    assert gen.DRIVER in ("serve", "train")
    assert limits and all(v > 0 for v in limits.values())
    assert run.metrics_of(manifest, cell, False)
    assert run.metrics_of(manifest, cell, True)


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    MANIFEST["end_to_end"] + MANIFEST["per_layer"]])
def test_reader_reads_nothing_from_an_empty_run(metric):
    mc = json.load(open(os.path.join(ROOT, MANIFEST["configs"][0]["file"])))
    assert run.reader(metric)({"mc": mc, "peak": PEAK}) is None
