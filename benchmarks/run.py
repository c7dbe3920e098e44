"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = per-query wall
time where meaningful, 0.0 for pure-quality measurements). Suites that
measure through the serving runtime additionally flush machine-readable
``ROWJSON,<record>`` lines as each cell completes -- `KERNEL_ROW_SCHEMA`
(kernels + qps_recall kernel-mode lane), `SHARDED_ROW_SCHEMA` (qps_recall
device sweep) and `HOSTIO_ROW_SCHEMA` (hostio lane), `FAULT_ROW_SCHEMA`
(faults lane, incl. the per-phase telemetry block); the CSV `derived`
column carries the same numbers flattened for spreadsheets.

``--out TEMPLATE`` additionally writes ONE consolidated JSON artifact per
suite -- the machine-readable side of the run, so CI (and anyone diffing
two runs) gets a single schema-versioned document instead of grepping
stdout::

    {"schema_version": 1, "suite": "faults", "rows": [<ROWJSON dicts>],
     "csv": ["name,us,derived", ...], "wall_s": 12.3}

TEMPLATE must contain a ``<suite>`` (or ``{suite}``) placeholder when more
than one suite runs; e.g. ``--out 'BENCH_<suite>.json'`` yields
``BENCH_faults.json`` etc. Corpus size scales down for CI via the
``REPRO_BENCH_N`` env var (see `common.bench_dataset`).

Run everything: ``python -m benchmarks.run``; one suite by name:
``python -m benchmarks.run hostio``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time

ARTIFACT_SCHEMA_VERSION = 1


class _RowTee(io.TextIOBase):
    """stdout tee that harvests ``ROWJSON,{...}`` lines while passing
    everything through unchanged (benches print progressively; the
    console output must stay identical with or without --out)."""

    def __init__(self, real) -> None:
        self._real = real
        self._buf = ""
        self.rows: list[dict] = []

    def write(self, s: str) -> int:
        n = self._real.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("ROWJSON,"):
                # Malformed payloads are a bench bug: fail loudly rather
                # than shipping a silently incomplete artifact.
                self.rows.append(json.loads(line[len("ROWJSON,"):]))
        return n

    def flush(self) -> None:
        self._real.flush()


def _artifact_path(template: str, suite: str) -> str:
    for ph in ("<suite>", "{suite}"):
        if ph in template:
            return template.replace(ph, suite)
    return template


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("suite", nargs="?", default=None,
                    help="run one suite by name (default: all)")
    ap.add_argument("--out", default=None, metavar="TEMPLATE",
                    help="write a consolidated JSON artifact per suite; "
                         "TEMPLATE's <suite> (or {suite}) placeholder is "
                         "replaced by the suite name")
    args = ap.parse_args()

    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()

    from . import (
        bench_ablations,
        bench_compression,
        bench_faults,
        bench_hostio,
        bench_iterations,
        bench_kernels,
        bench_mutation,
        bench_qps_recall,
        bench_variants,
    )

    suites = [
        ("qps_recall", bench_qps_recall),   # incl. the kernel-mode serving lane
        ("variants", bench_variants),
        ("compression", bench_compression),
        ("iterations", bench_iterations),
        ("kernels", bench_kernels),         # incl. the in-executor kernel lane
        ("hostio", bench_hostio),           # host-I/O subsystem sweep
        ("faults", bench_faults),           # scripted fault-schedule serving
        ("mutation", bench_mutation),       # streaming insert/delete serving
        ("ablations", bench_ablations),
    ]
    only = args.suite
    if only and only not in {name for name, _ in suites}:
        print(f"unknown suite {only!r}; have: "
              f"{', '.join(name for name, _ in suites)}", file=sys.stderr)
        sys.exit(2)
    selected = [(n, m) for n, m in suites if not only or only == n]
    if args.out and len(selected) > 1 and \
            _artifact_path(args.out, "x") == args.out:
        print("--out needs a <suite> placeholder when running multiple "
              "suites (artifacts would overwrite each other)",
              file=sys.stderr)
        sys.exit(2)

    print("name,us_per_call,derived")
    rows = []
    suite_csv: list[str] = []

    def report(name: str, us: float, derived: str) -> None:
        line = f"{name},{us:.1f},{derived}"
        rows.append(line)
        suite_csv.append(line)
        print(line, flush=True)

    for name, mod in selected:
        suite_csv = []
        tee = _RowTee(sys.stdout)
        t0 = time.time()
        with contextlib.redirect_stdout(tee):
            mod.run(report)
        wall = time.time() - t0
        print(f"# suite {name} done in {wall:.0f}s", flush=True)
        if args.out:
            path = _artifact_path(args.out, name)
            with open(path, "w") as f:
                json.dump({
                    "schema_version": ARTIFACT_SCHEMA_VERSION,
                    "suite": name,
                    "rows": tee.rows,
                    "csv": suite_csv,
                    "wall_s": wall,
                }, f, indent=2)
            print(f"# artifact: {path} ({len(tee.rows)} ROWJSON rows, "
                  f"{len(suite_csv)} csv rows)", flush=True)
    print(f"# {len(rows)} rows")


if __name__ == "__main__":
    main()
