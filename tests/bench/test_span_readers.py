"""The per-layer readers of the serve loop's host records (spans, the
host gap and the window's compiles) each read a finite number from a
small run of their own cell."""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from test_bench_harness import _tiny  # noqa: E402

READERS = ("host_gap_share.batch", "host_gap_share.chat", "prefill_p90_s",
           "window_compiles")
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
CASES = [(cell, m["name"]) for m in BENCH_JSON["per_layer"]
         if m["name"] in READERS for cell in m["workloads"]]


@pytest.fixture(scope="module")
def contexts():
    """One small unverified run per cell, shared by its readers."""
    import jax

    runs = {}

    def run(cell):
        if cell not in runs:
            runs[cell] = harness.run_cell(
                _tiny(cell), 2**32 + 9, 1.0, False, jax.devices(),
                time.monotonic(), verify=False)["ctx"]
        return runs[cell]

    return run


def test_every_reader_has_a_cell():
    assert {name for _, name in CASES} == set(READERS)


@pytest.mark.parametrize("cell,name", CASES)
def test_reader_reads_a_finite_number(contexts, cell, name):
    ctx = contexts(cell)
    value = harness.reader(name)(ctx)
    assert value is not None and math.isfinite(value), (name, value)
    assert value >= 0
    if name.startswith("host_gap_share"):
        assert value <= 100


@pytest.mark.parametrize("name", ["host_gap_share.batch",
                                  "host_gap_share.chat", "window_compiles"])
def test_reader_reads_nothing_where_the_program_keeps_no_record(name):
    from types import SimpleNamespace

    ctx = {"report": SimpleNamespace(wall_s=1.0), "results": []}
    assert harness.reader(name)(ctx) is None
