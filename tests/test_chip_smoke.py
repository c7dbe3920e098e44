"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, kernels in
interpret mode, and its refusal to report anything without a TPU."""
import os
import sys
from pathlib import Path


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Shape(d=16, R=8, m=4, L_build=16, t=32, k=5, batch=8,
                        batches=2)


def test_chip_smoke_phases_rehearse_on_cpu(monkeypatch, capsys):
    from repro.kernels.common import interpret_mode

    assert interpret_mode()
    chip_smoke.phase_answers(240, seed=0, shape=TINY, host_workers=1)
    # A tiny VMEM budget puts the DMA-phase codes past it, as 1M rows of
    # m = 32 codes are past the real one.
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "1024")
    chip_smoke.phase_deployment(600, 300, seed=0, shape=TINY, host_workers=1)
    out = capsys.readouterr().out
    assert '"ids_equal_reference": true' in out
    assert '"fused_codes": "dma"' in out
    assert '"ok"' not in out


def test_chip_smoke_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert captured.out == ""


def test_compile_cache_dir(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins untouched; else the fixed repo dir."""
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.setup_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.DEFAULT_DIR.parent == Path(REPO)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
