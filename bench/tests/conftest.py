"""CPU tests of the benchmark harness at tiny sizes.

The fixture cells under fixtures/tiny/ are defined only by their own
BENCHMARK.json, configuration and traffic files; drivers, generators,
references and metric readers come from this checkout's bench/.
"""
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"


@pytest.fixture
def tiny_cell():
    from bench.lib.cell import load_cell
    return lambda name: load_cell(name, TINY)


@pytest.fixture
def run_tiny(tmp_path):
    """Run a tiny cell through the whole harness on the CPU."""
    from bench.lib.cell import load_cell
    from bench.lib.harness import run_cell

    def run(name, seed=5, seconds=0.5, trace=False, after_setup=None,
            root=TINY):
        cell = load_cell(name, root)
        return run_cell(cell, seed, seconds, trace, time.perf_counter(),
                        after_setup=after_setup, out_dir=tmp_path,
                        compile_cache=False)
    return run
