"""PERF.md accounts for everything `BENCHMARK.json` names.

PERF.md is the one account of speed: each configuration and cell with
its "why", each metric with its layer. A name the benchmark declares
that PERF.md does not hold in backticks was added, renamed or dropped
without that account.
"""

import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
PERF_MD = (REPO / "PERF.md").read_text()
NAMES = [(kind, entry["name"])
         for kind in ("configs", "workloads", "end_to_end", "per_layer")
         for entry in BENCHMARK[kind]]


@pytest.mark.parametrize("kind,name", NAMES)
def test_perf_md_names_what_the_benchmark_declares(kind, name):
    assert f"`{name}`" in PERF_MD, (
        f"BENCHMARK.json {kind} entry {name!r} is not in PERF.md")
