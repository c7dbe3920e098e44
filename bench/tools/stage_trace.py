"""Per-stage device time of a cell's search, and a small trace with stages.

    python3 bench/tools/stage_trace.py --workload <cell> --seeds <n>... --seconds <s> --traced <k>
    python3 bench/tools/stage_trace.py --record <dir>

For each seed, the first makes the cell's index once, then serves one
untraced window and `--traced` traced ones over it through the harness's
path (`runner.Session`), and prints a JSON line per window: `qps`, each
drain's wall time and, for a traced window, each stage's device time per
batch (`bench/harness/stages.py`), the share of the search module's time
that no stage claims, the cell's per-layer metrics, and for each drain the
device's busy time and the summed time of each program span (`bang.*`)
inside it, so that a slow drain can be read. It checks no answer:
`bench/run.py` does.

The second records a small trace for the harness's tests: the base
configuration's generator, graph maker and placement at N = 65536, three
batches of 64 with the telemetry tracer attached, written to
`<dir>/v5e_small_stages.xplane.pb` (gzip it) with the executor's stage map
in `<dir>/v5e_small_stages.json`.

Both run on the chip and exit 2 without a TPU.
"""
import argparse
import collections
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import runner, spec, stages, trace  # noqa: E402
from bench.harness.executor import TimedExecutor  # noqa: E402


def drain_profiles(events: dict) -> list[dict]:
    """Each `bench.drain`'s wall time, device busy time and summed program
    spans inside it, in milliseconds."""
    host = events["host"]
    ops = [(s, e) for lines in events["devices"].values()
           for _, s, e in trace._leaves(lines[trace.OPS_LINE])]
    out = []
    for s, e in sorted((s, e) for n, s, e in host if n == "bench.drain"):
        busy = trace._merge(trace._clip(ops, s, e))
        spans: collections.Counter = collections.Counter()
        for n, bs, be in host:
            if n.startswith("bang.") and be > s and bs < e:
                spans[n] += (min(be, e) - max(bs, s)) / 1e6
        out.append({"wall_ms": (e - s) / 1e6,
                    "device_busy_ms": sum(b - a for a, b in busy) / 1e6,
                    "spans_ms": dict(spans.most_common())})
    return out


def cell(workload: str, seed: int, seconds: float, traced: int) -> None:
    s = runner.Session(workload, seed, t_process=time.perf_counter())
    ex = runner.program_executor(s.index, s.config)
    for i in range(traced + 1):
        served = s.serve(ex, seconds, trace=i > 0)
        win = served["window"]
        n = len(win.batches)
        out = {"seed": seed, "window": i, "traced": i > 0,
               "qps": int(np.sum(np.all(win.ids >= 0, 1))) / win.seconds,
               "batches": n, "drain_s": win.drain_s}
        if i > 0:
            (pb,) = glob.glob(os.path.join(served["trace_dir"], "**",
                                           "*.xplane.pb"), recursive=True)
            events = trace.load_events(pb)
            shutil.rmtree(served["trace_dir"], ignore_errors=True)
            st = stages.reduce_stages(events, ex.stage_map())
            reduced = trace.reduce(events)
            rec = runner.RunRecord(
                config=s.config, traffic=s.traffic, window=win,
                setup_s=served["setup_s"], recall=np.zeros(1),
                device_kind=s.devs[0].device_kind, spans=served["spans"],
                window_t0_us=served["window_t0_us"], trace=reduced)
            out.update(
                stage_ms_per_batch={k: v * 1e3 / n
                                    for k, v in st["stages"].items()},
                module_ms_per_batch=st["module_s"] * 1e3 / n,
                unclaimed_share=st["unclaimed_share"],
                busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                per_layer={m["name"]: spec.metric_reader(m["name"])(rec)
                           for m in s.cell["per_layer"]},
                drains=drain_profiles(events))
        print(json.dumps(out), flush=True)


def record(out_dir: str) -> None:
    import jax

    from repro.core import SearchConfig
    from repro.runtime import ServePipeline
    from repro.runtime.telemetry import Telemetry

    config = json.loads(
        (spec.ROOT / "bench/configs/deeplike-10m-base.json").read_text())
    config.update(n=65536, queries=256, max_batch=64)
    config["graph"] = dict(config["graph"], block=4096, kmeans_sample=16384,
                           chunk=8192, prune_chunk=2048)
    index, _, queries = runner.make_index(config, 1, runner.Ledger())
    ex = runner.program_executor(index, config)
    tex = TimedExecutor(ex, annotate=True)
    pipe = ServePipeline(tex, k=config["k"], cfg=SearchConfig(t=config["t"]),
                         max_batch=64, telemetry=Telemetry.create(trace=True))
    pipe.submit(queries[:64])
    pipe.drain()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tmp, profiler_options=runner.profile_options())
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                pipe.submit(queries[64 * i:64 * (i + 1)])
            with jax.profiler.TraceAnnotation("bench.drain"):
                pipe.drain()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    pipe.close()
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    pb = sorted(Path(tmp).glob("**/*.xplane.pb"))[-1]
    shutil.copyfile(pb, out / "v5e_small_stages.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    (out / "v5e_small_stages.json").write_text(
        json.dumps({"stage_map": ex.stage_map()}, sort_keys=True))
    print(json.dumps({"out": str(out), "bytes": (
        out / "v5e_small_stages.xplane.pb").stat().st_size}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--record", help="directory for the small trace")
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--traced", type=int, default=1)
    args = ap.parse_args(argv)
    runner.setup_jax(spec.ROOT)
    try:
        runner.devices(1)
    except runner.NoDevice as e:
        print(f"stage_trace: {e}", file=sys.stderr)
        return 2
    if args.record:
        record(args.record)
    else:
        for seed in args.seeds:
            cell(args.workload, seed, args.seconds, args.traced)
            gc.collect()            # the last seed's index, before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
