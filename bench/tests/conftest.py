"""Fixtures for the harness's own tests (CPU; `python -m pytest bench/tests`).

`tiny_root` is a checkout-like directory whose BENCHMARK.json names the real
cells, with every configuration cut to a size the CPU runs in seconds.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {"n": 8192, "queries": 256, "max_batch": 32, "t": 32}
TINY_GRAPH = {"block": 1024, "kmeans_sample": 4096, "chunk": 1024,
              "prune_chunk": 512}


def make_root(dest: Path) -> Path:
    (dest / "bench" / "configs").mkdir(parents=True)
    for sub in ("metrics", "traffic"):
        shutil.copytree(REPO / "bench" / sub, dest / "bench" / sub)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in (REPO / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(TINY)
        cfg["graph"] = {**cfg["graph"], **TINY_GRAPH}
        (dest / "bench" / "configs" / path.name).write_text(json.dumps(cfg))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("root"))
