"""`chip_smoke.py` off the chip: it refuses the CPU, and its phases run
end to end at tiny sizes (the chip runs them at full width)."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import smoke_config
from repro.tiering import KVTierConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "platform=cpu" in out.stdout


def test_serve_phase_tiny(chip_smoke):
    chip_smoke.serve_phase(smoke_config("stablelm-3b"), batch=2,
                           prompt_len=8, max_new=4, n_requests=4)


def test_tiered_kv_pathways_fire_tiny(chip_smoke):
    """The chip's page count and slot count with tiny pages: the
    pathways depend on those ratios, not on the page bytes."""
    kvcfg = KVTierConfig(n_pages=chip_smoke.N_PAGES,
                         fast_slots=chip_smoke.FAST_SLOTS,
                         page_tokens=chip_smoke.PAGE_TOKENS, kv_heads=2,
                         head_dim=8, n_layers=2)
    kv, by_compaction, by_flush = chip_smoke.drive_tiered_kv(kvcfg)
    assert kv.clock.retained > 0
    assert by_compaction > 0 and by_flush > 0


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise a fixed,
    git-ignored directory inside the checkout."""
    import jax

    from repro.launch.compile_cache import CACHE_DIR, setup_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert setup_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert setup_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert CACHE_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{CACHE_DIR.name}/" in ignored
