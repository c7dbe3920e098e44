"""The `exact-backlog` cell at a CPU size: the program passes the check, the
control and every fault fail it, and `exact_roofline` counts what a hand
count gives.

The check is the one every cell has (`runner.judge`). Here it judges the
search's own arithmetic: the returned distances are the worklist's
`qn + vn - 2 q.v` at `Precision.HIGHEST`, with no re-rank after it, so
`dist_gap <= 1e-3` holds them to float32 rounding (about 1e-5 on the CPU).
A default-precision dot on the chip rounds its inputs to bfloat16, as the
control does (0.36 to 0.58), and fails it.
"""
import types

import numpy as np
import pytest

from bench.harness import controls, runner, spec, work

CELL = "exact-backlog"


@pytest.mark.parametrize("seed", [2**34 + 3, 5])
def test_program_is_correct(tiny_root, seed):
    out = runner.run(CELL, seed, 2.0, False, t_process=0.0, root=tiny_root,
                     require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # float32 rounding of the search's own distances, far under the limit
    assert out["checks"]["dist_gap"]["value"] < 1e-4


@pytest.mark.parametrize("kind", controls.KINDS)
def test_control_and_faults_are_not_correct(tiny_root, kind):
    out = runner.run(CELL, 11, 1.0, False, t_process=0.0, root=tiny_root,
                     require_tpu=False, wrap=controls.make(kind))
    assert not out["correct"], (kind, out["checks"])


def test_cell_reads_the_exact_roofline_and_not_the_pq_one(tiny_root):
    names = {m["name"] for m in spec.resolve(CELL, tiny_root)["per_layer"]}
    assert "exact_roofline" in names
    assert not names & {"search_roofline", "host_gather_ms",
                        "rerank_gather_ms"}


def test_exact_work_hand_count():
    from bench.metrics import exact_roofline

    # 2 queries, 5 expansions, R 4, d 8.
    got = exact_roofline.exact_work(5, 2, R=4, d=8)
    per_exp_bytes = 4 * 4 + 4 * 8 * 4              # ids, neighbour vectors
    assert got["bytes"] == 5 * per_exp_bytes + 2 * 8 * 4
    assert got["ops"] == 5 * 3 * 4 * 8


def test_exact_roofline_reads_the_window_against_the_module_time():
    read = spec.metric_reader("exact_roofline")
    config = {"R": 64, "d": 96}
    batches = [{"size": 1024, "hops": np.full(1024, 56)}] * 2
    run = types.SimpleNamespace(
        config=config, window=types.SimpleNamespace(batches=batches),
        trace={"module_s": 0.6}, device_kind="TPU v5 lite")
    # one batch at t 32 reads 1,024 x 56 x (256 + 24,576) B + 1,024 x 384 B
    need = 2 * (1024 * 56 * (64 * 4 + 64 * 96 * 4) + 1024 * 96 * 4)
    assert need == 2 * 1_424_359_424
    want = 100.0 * need / work.peaks("TPU v5 lite")["hbm_bytes_per_s"] / 0.6
    assert read(run) == pytest.approx(want)
    assert read(types.SimpleNamespace(
        config=config, window=run.window, trace=None,
        device_kind="TPU v5 lite")) is None
