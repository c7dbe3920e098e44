"""The benchmark's one command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell in BENCHMARK.json, makes the index from the seed, warms up
every shape, serves the cell's traffic for `--seconds`, checks every answer
against the plain reference, and prints one JSON line as the last line of
standard output. With `--trace 1` the metrics are the cell's per-layer ones,
read from a profiler trace of the window. Exits 2 without printing a result
when JAX finds no TPU, or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = runner.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_process=T_PROCESS)
    except (runner.NoDevice, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        bound = f"min {c['min']}" if "min" in c else f"max {c['max']}"
        print(f"check {name} = {c['value']} ({bound})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
