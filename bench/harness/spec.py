"""Resolving a cell by name: `BENCHMARK.json`, and the files it points at.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

    bench/configs/<config>.json   (the path is the config entry's `file`)
    bench/traffic/<traffic>.json
    bench/metrics/<metric>.py     (defines `read(run) -> float | None`)

so a later cell, configuration or metric is added as files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class SpecError(ValueError):
    """The cell, or a file it names, is missing or malformed."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}")


def resolve(workload: str, root: Path = ROOT) -> dict:
    """The cell `workload` with its configuration, traffic and metrics."""
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], workload, "workload")
    entry = _by_name(bench["configs"], cell["config"], "config")
    config = json.loads((root / entry["file"]).read_text())
    traffic_path = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"traffic mix {cell['traffic']!r}: {traffic_path} "
                        "not found")

    def cell_metrics(group: str) -> list[dict]:
        return [m for m in bench[group]
                if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": config,
        "traffic": json.loads(traffic_path.read_text()),
        "end_to_end": cell_metrics("end_to_end"),
        "per_layer": cell_metrics("per_layer"),
    }


def metric_reader(name: str, root: Path = ROOT):
    """`read(run)` from bench/metrics/<name>.py."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r}: {path} not found")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
