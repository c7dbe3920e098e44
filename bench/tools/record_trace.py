"""Record a small profiler trace of the serving path, for the reduction's test.

    python3 bench/tools/record_trace.py --out v5e_small.xplane.pb  (then gzip it)

Makes a small index with the base configuration's generator and graph
maker (N = 65536), in the in-memory placement, serves three batches of 64 queries through the harness's
path with its `bench.*` spans, and writes the `.xplane.pb` of that window to
`--out`. Runs on the chip; exits 2 without a TPU.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import runner, spec  # noqa: E402
from bench.harness.executor import TimedExecutor  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    runner.setup_jax(spec.ROOT)
    try:
        runner.devices(1)
    except runner.NoDevice as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.core import SearchConfig
    from repro.runtime import ServePipeline

    config = json.loads(
        (spec.ROOT / "bench/configs/deeplike-10m-base.json").read_text())
    config.update(n=65536, queries=256, max_batch=64, variant="inmem")
    config["graph"] = dict(config["graph"], block=4096, kmeans_sample=16384,
                           chunk=8192, prune_chunk=2048)
    index, _, queries = runner.make_index(config, 1, runner.Ledger())
    tex = TimedExecutor(runner.program_executor(index, config), annotate=True)
    pipe = ServePipeline(tex, k=config["k"], cfg=SearchConfig(t=config["t"]),
                         max_batch=64)
    pipe.submit(queries[:64])
    pipe.drain()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tmp, profiler_options=runner.profile_options())
    with jax.profiler.TraceAnnotation(runner.tracelib.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                pipe.submit(queries[64 * i:64 * (i + 1)])
            with jax.profiler.TraceAnnotation("bench.drain"):
                pipe.drain()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    pipe.close()
    pb = sorted(Path(tmp).glob("**/*.xplane.pb"))[-1]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(pb, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"out": args.out, "bytes": Path(args.out).stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
