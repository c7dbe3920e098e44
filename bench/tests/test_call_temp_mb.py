"""call_temp_mb reads the `temp_bytes` of the compile span of the bucket the
window ran in, and nothing where the program's span lacks it."""
import types

from bench.harness import spec

read = spec.metric_reader("call_temp_mb")


def run_with(spans, buckets=(1024,)):
    window = types.SimpleNamespace(batches=[{"bucket": b} for b in buckets])
    return types.SimpleNamespace(spans=spans, window=window)


def compile_span(bucket, **args):
    return {"ph": "X", "name": "compile", "ts": 5.0, "dur": 2.0,
            "args": {"bucket": bucket, "k": 10, **args}}


def test_reads_the_window_buckets_compile_span_in_mb():
    spans = [compile_span(8, temp_bytes=9_000_000_000, argument_bytes=1),
             compile_span(1024, temp_bytes=446_590_464,
                          argument_bytes=6_720_491_520),
             {"ph": "X", "name": "drain", "ts": 9.0, "dur": 1.0, "args": {}}]
    assert read(run_with(spans)) == 446.590464


def test_none_without_the_figure_or_the_span():
    assert read(run_with([compile_span(1024)])) is None     # older program
    assert read(run_with([compile_span(8, temp_bytes=5)])) is None
    assert read(run_with(None)) is None                      # untraced run
    assert read(run_with([compile_span(1024, temp_bytes=5)], ())) is None
