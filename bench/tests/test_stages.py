"""The per-stage reduction (bench/harness/stages.py): hand-made events, and a
small trace recorded on a TPU v5e with the executor's stage map beside it
(bench/tests/data/v5e_small_stages.*, `bench/tools/stage_trace.py
--record`). Also pins what `trace.reduce` gives on the first recorded
trace, so that the program's new host spans cannot move it."""
import json
from pathlib import Path

import pytest

from bench.harness import stages, trace

DATA = Path(__file__).with_name("data")
STAGES = ("bang.table", "bang.fetch", "bang.bloom", "bang.step",
          "bang.history", "bang.rerank")


def op(name: str, s: int, e: int, opcode: str = "add"):
    return (f"%{name} = f32[2]{{0}} {opcode}(%x)", s, e)


def test_stage_time_is_leaf_op_time_inside_module_runs_in_the_window():
    ev = {"devices": {"/device:TPU:0": {
        trace.OPS_LINE: [
            op("a.1", 0, 30), op("b.2", 30, 50), op("c.3", 50, 60),
            op("while.4", 0, 60, "while"),          # a container: left out
            op("a.1", 100, 130), op("d.5", 130, 200)],
        trace.MODULES_LINE: [("jit_pipeline(1)", 0, 60),
                             ("jit_pipeline(1)", 100, 140),
                             ("jit_other", 140, 200)]}},
        "host": [(trace.WINDOW_SPAN, 10, 190)]}
    got = stages.reduce_stages(ev, {"a.1": "bang.step", "b.2": "bang.bloom",
                                    "d.5": "bang.rerank", "while.4": "x"})
    # window [10, 190]; module runs [10, 60] and [100, 140]
    assert got["module_s"] == pytest.approx(90e-9)
    assert got["stages"] == pytest.approx({
        "bang.step": 20e-9 + 30e-9,         # a.1 from 10, and all of it later
        "bang.bloom": 20e-9,
        "bang.rerank": 10e-9,               # d.5 up to the module's end
    })
    # c.3 has no stage: its 10 ns, plus nothing else, is unclaimed
    assert got["unclaimed_s"] == pytest.approx(10e-9)
    assert got["unclaimed_share"] == pytest.approx(10 / 90)


def test_no_device_events_reduce_to_nothing():
    assert stages.reduce_stages({"devices": {}, "host": []}, {}) == {}


def test_first_recorded_trace_reduces_as_before():
    got = trace.reduce(trace.load_events(str(DATA / "v5e_small.xplane.pb.gz")))
    assert got["busy_s"] == pytest.approx(1.197389843, rel=1e-9)
    assert got["window_s"] == pytest.approx(1.246874454, rel=1e-9)
    assert got["module_s"] == pytest.approx(1.197789659, rel=1e-9)
    ops = got["breakdown"]["device_ops"]
    assert [n for n, _ in ops[:3]] == [
        "fusion.82 kCustom f32[131072]",
        "dynamic-update-slice.17 dynamic-update-slice u8[1,64,399887]",
        "dynamic-update-slice.16 dynamic-update-slice u8[25592768]"]
    assert ops[0][1] == pytest.approx(0.392880259, rel=1e-9)
    assert [n for n, _ in got["breakdown"]["idle_gaps"]] == (
        ["bench.idle"] * 3 + ["bench.drain"] + ["bench.dispatch"] * 4
        + ["bench.finish"] * 2)


def test_recorded_trace_with_stages():
    ev = trace.load_events(str(DATA / "v5e_small_stages.xplane.pb.gz"))
    stage_map = json.loads(
        (DATA / "v5e_small_stages.json").read_text())["stage_map"]
    got = stages.reduce_stages(ev, stage_map)
    assert set(got["stages"]) == set(STAGES)
    assert got["module_s"] == pytest.approx(trace.reduce(ev)["module_s"],
                                            rel=1e-9)
    # the stages' ops lie inside the module's runs and do not overlap:
    # with what no stage claims they make up the module's time, and the
    # stages claim at least 90% of it
    assert 0 <= got["unclaimed_s"] <= 0.1 * got["module_s"]
    # the program's spans are on the profiler's host plane
    host = {n for n, _, _ in ev["host"]}
    assert {"bang.drain", "bang.dispatch", "bang.gather",
            "bang.rerank_gather"} <= host
