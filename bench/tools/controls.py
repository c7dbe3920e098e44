"""Readings that set the check's limits: the program, its control, faults.

    python3 bench/tools/controls.py --workload base-backlog --seeds 21,22,23

For each seed, one process makes the cell's index (the reference runs first,
on the corpus as made), then serves a short window of the cell's own traffic
through each of: the program ("program"), the plain reference in bfloat16
in the program's place ("control"), and the program with its answers broken
("stale", "half", "altered"; see bench/harness/controls.py). Where only the
control serves, the graph is not made: the control searches the corpus
itself. Prints each window's checks. Runs on the chip; exits 2 without a
TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import controls, runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--kinds", default="program," + ",".join(controls.KINDS))
    args = ap.parse_args(argv)
    kinds = args.kinds.split(",")
    for seed in map(int, args.seeds.split(",")):
        try:
            s = runner.Session(args.workload, seed, t_process=T_PROCESS,
                               truth=True, graph=kinds != ["control"])
        except (runner.NoDevice, spec.SpecError) as e:
            print(f"controls: {e}", file=sys.stderr)
            return 2
        for kind in kinds:
            ex = None
            if kind != "control":
                ex = runner.program_executor(s.index, s.config)
            if kind != "program":
                ex = controls.make(kind)(ex, s)
            win = s.serve(ex, args.seconds)["window"]
            checks, _ = s.judge(win)
            print(json.dumps({"seed": seed, "kind": kind,
                              "correct": runner.passed(checks),
                              "queries": len(win.pool), "checks": checks}),
                  flush=True)
            del ex, win
            gc.collect()
        del s
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
