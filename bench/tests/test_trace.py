"""The trace reduction: hand-made events, and a small trace recorded on a
TPU v5e (bench/tests/data/v5e_small.xplane.pb.gz)."""
from pathlib import Path

import pytest

from bench.harness import trace

SMALL = Path(__file__).with_name("data") / "v5e_small.xplane.pb.gz"


def events(ops, modules=(), host=()):
    return {"devices": {"/device:TPU:0": {trace.OPS_LINE: list(ops),
                                         trace.MODULES_LINE: list(modules)}},
            "host": list(host)}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ev = events(
        ops=[("a", 0, 30), ("b", 20, 50), ("a", 80, 90), ("c", 140, 200)],
        modules=[("jit_pipeline(1)", 0, 95), ("jit_other", 100, 200)],
        host=[(trace.WINDOW_SPAN, 10, 150), ("bench.finish", 55, 75),
              ("bench.dispatch", 90, 140), ("bench.drain", 0, 150)])
    got = trace.reduce(ev)
    # window [10, 150]: busy [10, 50] + [80, 90] + [140, 150] = 60 ns
    assert got["window_s"] == pytest.approx(140e-9)
    assert got["busy_s"] == pytest.approx(60e-9)
    assert got["module_s"] == pytest.approx(85e-9)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops == pytest.approx({"a": 30e-9, "b": 30e-9, "c": 10e-9})
    gaps = got["breakdown"]["idle_gaps"]
    # gaps [90, 140] (host in dispatch) and [50, 80] (host in finish,
    # the innermost bench span at its midpoint)
    assert gaps == [["bench.dispatch", pytest.approx(50e-9)],
                    ["bench.finish", pytest.approx(30e-9)]]


def test_no_device_events_reduce_to_nothing():
    assert trace.reduce({"devices": {}, "host": []}) == {}


def test_recorded_v5e_trace():
    ev = trace.load_events(str(SMALL))
    assert any(n.startswith("/device:TPU") for n in ev["devices"])
    got = trace.reduce(ev)
    assert 0 < got["busy_s"] <= got["window_s"]
    # as reduced when the trace was recorded (PERF.md)
    assert got["busy_s"] == pytest.approx(1.197389843, rel=1e-9)
    assert got["window_s"] == pytest.approx(1.246874454, rel=1e-9)
    assert got["module_s"] > 0
    names = [n for n, _ in got["breakdown"]["device_ops"]]
    assert names and all(isinstance(n, str) for n in names)
    gaps = got["breakdown"]["idle_gaps"]
    assert gaps and gaps[0][1] >= gaps[-1][1]
    # the three drains, and the sleeps between them, are named
    assert {"bench.idle", "bench.drain"} & {n for n, _ in gaps}
