"""Megakernel autotuner (repro.kernels.autotune): winner persistence,
compile-cache-key reproduction, corrupt-file fallback, the sweep itself,
and the latency-hiding XLA flag setup.

The acceptance contract under test: winners persist to JSON keyed by
(device kind, bucket, R, m), and a reloaded file reproduces the *same*
executor compile-cache keys -- tuned configs ride the key, so
differently-tuned executables can never be confused, and serving after a
restart recompiles into exactly the executables the sweep measured.
"""
import json

import numpy as np
import pytest

from repro.core import SearchConfig
from repro.kernels import autotune as at
from repro.runtime import SearchExecutor

R, M = 16, 8          # small_ann_index build parameters (R=16, m=8)


def _search_keys(idx, cache, queries):
    """Compile-cache keys after one fused search through a fresh executor."""
    ex = SearchExecutor.from_index(idx, variant="inmem", autotune=cache)
    cfg = SearchConfig(t=16, bloom_z=4096, kernel_mode="fused")
    ids, _ = ex.search(queries, 5, cfg=cfg)
    return set(ex._cache), np.asarray(ids)


def test_roundtrip_reproduces_compile_cache_keys(small_ann_index, tmp_path,
                                                 rng):
    data, idx = small_ann_index
    queries = rng.standard_normal((6, data.shape[1])).astype(np.float32)
    dk = at.device_kind()
    cache = at.AutotuneCache()
    # bucket 8 serves the 6-query batch; tile 64 forces the DMA placement.
    # eager stays at the caller's default: the placement knob is bit-exact,
    # so this winner must not change results (asserted below); the eager
    # knob is the §4.6 algorithmic flavour and may.
    cache.put(dk, 8, R, M, eager=True, codes_tile_rows=64, per_hop_us=1.0)

    keys1, ids1 = _search_keys(idx, cache, queries)
    path = tmp_path / "winners.json"
    cache.save(path)
    keys2, ids2 = _search_keys(idx, at.AutotuneCache.load(path), queries)
    assert keys1 == keys2                      # the acceptance criterion
    np.testing.assert_array_equal(ids1, ids2)

    # The winner really rode the key: the executable was built for the
    # tuned config, not the caller's.
    (key,) = keys1
    cfg_in_key = next(c for c in key if isinstance(c, SearchConfig))
    assert cfg_in_key.codes_tile_rows == 64 and cfg_in_key.eager is True
    # ... and an untuned executor keys differently but serves the same ids
    # (DMA vs resident placement is bit-exact).
    keys3, ids3 = _search_keys(idx, None, queries)
    assert keys3 != keys1
    np.testing.assert_array_equal(ids1, ids3)

    # A winner for a *different* shape leaves this executor untuned.
    other = at.AutotuneCache()
    other.put(dk, 128, R, M, eager=False, codes_tile_rows=64, per_hop_us=1.0)
    keys4, _ = _search_keys(idx, other, queries)
    assert keys4 == keys3


def test_cache_json_schema_and_key_format(tmp_path):
    cache = at.AutotuneCache()
    cache.put("TPU v4", 64, 32, 16, eager=True, codes_tile_rows=0,
              per_hop_us=12.5)
    path = tmp_path / "w.json"
    cache.save(path)
    raw = json.loads(path.read_text())
    assert raw["version"] == at.SCHEMA_VERSION
    assert raw["winners"] == {
        "TPU v4|bucket=64|R=32|m=16": {
            "eager": True, "codes_tile_rows": 0, "per_hop_us": 12.5,
        },
    }
    loaded = at.AutotuneCache.load(path, strict=True)
    assert len(loaded) == 1
    assert loaded.lookup("TPU v4", 64, 32, 16)["per_hop_us"] == 12.5
    assert loaded.lookup("TPU v4", 64, 32, 99) is None


@pytest.mark.parametrize("content", [
    "{not json",                                               # unparseable
    json.dumps([1, 2]),                                        # not an object
    json.dumps({"version": 99, "winners": {}}),                # bad version
    json.dumps({"version": 1, "winners": [1]}),                # bad winners
    json.dumps({"version": 1, "winners": {"k": {"eager": 1,    # int != bool
                "codes_tile_rows": 0, "per_hop_us": 1.0}}}),
    json.dumps({"version": 1, "winners": {"k": {"eager": True,  # missing field
                "per_hop_us": 1.0}}}),
    json.dumps({"version": 1, "winners": {"k": {"eager": True,  # negative tile
                "codes_tile_rows": -8, "per_hop_us": 1.0}}}),
])
def test_corrupt_cache_falls_back_to_defaults(tmp_path, content):
    """A bad tuning file can never take serving down: non-strict load warns
    and returns an empty cache (default configs); strict load (the CI
    schema check) raises instead."""
    path = tmp_path / "bad.json"
    path.write_text(content)
    with pytest.warns(UserWarning, match="falling back"):
        cache = at.AutotuneCache.load(path)
    assert len(cache) == 0
    with pytest.raises((ValueError, TypeError, KeyError)):
        at.AutotuneCache.load(path, strict=True)


def test_missing_cache_file_falls_back(tmp_path):
    with pytest.warns(UserWarning, match="falling back"):
        cache = at.AutotuneCache.load(tmp_path / "nope.json")
    assert len(cache) == 0
    with pytest.raises(OSError):
        at.AutotuneCache.load(tmp_path / "nope.json", strict=True)


def test_apply_replaces_only_on_winner():
    cache = at.AutotuneCache()
    cfg = SearchConfig(t=16, kernel_mode="fused")
    assert cache.apply(cfg, "cpu", 8, R, M) is cfg     # no winner: untouched
    cache.put("cpu", 8, R, M, eager=False, codes_tile_rows=32, per_hop_us=2.0)
    tuned = cache.apply(cfg, "cpu", 8, R, M)
    assert tuned.eager is False and tuned.codes_tile_rows == 32
    assert tuned.t == cfg.t and tuned.kernel_mode == "fused"
    assert cache.apply(cfg, "cpu", 16, R, M) is cfg    # other bucket: no


def test_default_tile_candidates(monkeypatch):
    # A block that fits VMEM sweeps both placements: resident, forced HBM.
    assert at.default_tile_candidates(1200, 8) == (0, 8)
    # Beyond the (forced) budget the auto placement already is HBM.
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2048")
    assert at.default_tile_candidates(1200, 8) == (0,)


def test_autotune_executor_sweep_records_winner(small_ann_index, rng):
    """The sweep times real fused searches, records exactly one winner for
    the queries' bucket, and leaves the executor's own autotune state as it
    found it (so sweeping a tuned executor cannot poison itself)."""
    data, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="inmem")
    queries = rng.standard_normal((4, data.shape[1])).astype(np.float32)
    cache = at.autotune_executor(
        ex, queries, k=4, t=16, repeats=1,
        tile_candidates=(0, 64), eager_options=(True,),
    )
    assert len(cache) == 1
    w = cache.lookup(at.device_kind(), ex._bucket_for(4), R, M)
    assert w is not None
    assert w["eager"] is True and w["codes_tile_rows"] in (0, 64)
    assert w["per_hop_us"] > 0
    assert ex._autotune is None                       # restored, not leaked
