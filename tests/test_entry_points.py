"""The serving CLI (`examples/serve_ann.py`) starts and answers correctly.

Each case runs the documented surface in its own process at a tiny size and
reads the recall it prints over every served query.
"""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--n", "400", "--dim", "16", "--batches", "2", "--batch-size", "8",
        "--max-batch", "8", "--t", "32"]


@pytest.mark.parametrize("extra", [
    ["--variant", "inmem"],
    ["--variant", "base"],
    ["--variant", "exact"],
    ["--variant", "base", "--host-workers", "2", "--prefetch"],
], ids=["inmem", "base", "exact", "base-hostio"])
def test_serve_ann_cli(extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "serve_ann.py"),
         *TINY, *extra],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    m = re.search(r"mean recall@10=([0-9.]+)", out.stdout)
    assert m, out.stdout
    assert float(m.group(1)) >= 0.9, out.stdout
