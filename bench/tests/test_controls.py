"""The check refuses the control and every fault the cells can have, and
passes the program: whole runs at a CPU size, without the chip."""
import pytest

from bench.harness import controls, runner


@pytest.mark.parametrize("seed", [2**34 + 3, 5])
def test_program_is_correct(tiny_root, seed):
    out = runner.run("base-backlog", seed, 2.0, False, t_process=0.0,
                     root=tiny_root, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("kind", controls.KINDS)
def test_control_and_faults_are_not_correct(tiny_root, kind):
    out = runner.run("base-backlog", 11, 1.0, False, t_process=0.0,
                     root=tiny_root, require_tpu=False,
                     wrap=controls.make(kind))
    assert not out["correct"], (kind, out["checks"])


def test_without_a_chip_the_run_refuses(tiny_root):
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(runner.NoDevice):
        runner.run("base-backlog", 1, 1.0, False, t_process=0.0,
                   root=tiny_root)


def _cli(cwd, *args):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "base-backlog",
         "--seed", str(2**33), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_chip_exits_nonzero_and_prints_nothing():
    from bench.tests.conftest import REPO

    got = _cli(REPO)
    assert got.returncode == 2
    assert got.stdout == ""


def test_command_with_only_the_benchmark_files_fails(tmp_path):
    import shutil

    from bench.tests.conftest import REPO

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _cli(tmp_path, "--trace", "1")
    assert got.returncode != 0
    assert got.stdout == ""
