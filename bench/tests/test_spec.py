"""Cells, configurations, mixes and metrics are found by name: a throwaway
one of each, added as files and entries only, runs end to end."""
import json

import pytest

from bench.harness import runner, spec
from bench.tests.conftest import make_root


def test_the_real_cells_resolve():
    bench = spec.load_benchmark()
    assert bench["workloads"]
    for cell in bench["workloads"]:
        got = spec.resolve(cell["name"])
        assert got["config"]["name"] == cell["config"]
        assert any(m["name"] == "setup_s" for m in got["end_to_end"])
        assert got["per_layer"]
        for m in got["end_to_end"] + got["per_layer"]:
            assert callable(spec.metric_reader(m["name"]))


def test_unknown_names_are_refused(tiny_root):
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such-cell", tiny_root)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric", tiny_root)


def test_added_config_mix_and_metric_run(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (root / "bench/configs/deeplike-10m-base.json").read_text())
    cfg.update(name="throwaway", variant="inmem", t=48)
    (root / "bench/configs/throwaway.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/single.json").write_text(json.dumps(
        {"arrivals": "backlog", "order": "cycle", "drain_batches": 1}))
    (root / "bench/metrics/queries_sent.py").write_text(
        "def read(run):\n    return float(len(run.window.pool))\n")
    bench["configs"].append({"name": "throwaway", "source": "a test",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "throwaway.single",
                               "config": "throwaway", "traffic": "single",
                               "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "queries_sent", "unit": "queries",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["throwaway.single"]})
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("throwaway.single")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = runner.run("throwaway.single", 3, 2.0, False, t_process=0.0,
                     root=root, require_tpu=False)
    assert out["correct"], out["checks"]
    sent = out["metrics"]["queries_sent"]["value"]
    assert sent > 0 and sent % 32 == 0   # whole drains of one full batch
    assert set(out["metrics"]) == {"queries_sent", "qps", "setup_s",
                                   "recall_at_10"}
    assert list(out)[-1] == "checks"


def test_an_unknown_mix_is_refused(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench/traffic/open.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_qps": 100.0}))
    bench["workloads"].append({"name": "open", "config": "deeplike-10m-base",
                               "traffic": "open", "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        runner.run("open", 3, 1.0, False, t_process=0.0, root=root,
                   require_tpu=False)
