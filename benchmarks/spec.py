"""Where the benchmark's data files are, found by the names in
BENCHMARK.json. A later PR adds entries and files; nothing here lists
a cell, a configuration, a generator, a metric or a reader by name."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load(path: Path) -> dict:
    with path.open() as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """One entry of `workloads`, joined with its own file
    (`workloads/<name>.json`), its configuration's file and the metrics
    BENCHMARK.json gives it."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    here = root / "benchmarks"

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        **entry,
        **_load(here / "workloads" / f"{name}.json"),
        "model": _load(root / cfg["file"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [
            {**m, **_load(here / "metrics" / f"{m['name']}.json")}
            for m in bench["per_layer"] if mine(m)],
    }


def peaks(device_kind: str) -> dict:
    table = _load(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(has {sorted(table)}): add the chip with its source")
    return table[device_kind]


def plugin(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`: an adapter, a traffic generator
    or a reader, by the name a data file gives."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")
