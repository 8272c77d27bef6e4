"""Reads the benchmark's data files: BENCHMARK.json and what it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in BENCHMARK.json:

    configs/<config>.json    the deployment (BENCHMARK.json gives the path)
    traffic/<traffic>.json   parameters of the one general generator
    metrics/<metric>.json    names a reader in readers/ and its arguments
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with the files it resolves to."""

    def __init__(self, workload: str, benchmark_json: str = ""):
        self.bench = _load(benchmark_json or os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; BENCHMARK.json has "
                f"{sorted(cells)}"
            )
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = next(
            c for c in self.bench["configs"] if c["name"] == self.entry["config"]
        )
        self.config = _load(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_traffic(self.entry["traffic"])

    def _in_cell(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._in_cell(m)]

    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self._in_cell(m)]


def load_traffic(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def load_metric(name: str) -> dict:
    """metrics/<name>.json: {"reader": <module in readers/>, "args": {...}}."""
    return _load(os.path.join(BENCH_DIR, "metrics", f"{name}.json"))


def load_peaks() -> dict:
    return _load(os.path.join(BENCH_DIR, "peaks.json"))
