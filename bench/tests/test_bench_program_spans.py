"""The readers of the program's own spans and counters, on traced tiny
cells whose BENCHMARK.json is a copy of the fixture's with the new
per-layer entries added: each gives its number traced, None untraced."""
import json
import shutil
import types

import pytest

from bench.lib.cell import load_benchmark, load_module
from bench.tests.conftest import ROOT, TINY

NEW = {"engine_host_ms_per_step.tps": "tiny.batch",
       "engine_host_ms_per_step.ttft": "tiny.chat",
       "engine_first_token_p95_ms.ttft": "tiny.chat",
       "kv_h2d_mib_per_read.kv": "tiny.kv_skew",
       "kv_host_fetch_ms_per_read.kv": "tiny.kv_skew",
       "ralt_host_ms_per_read.kv": "tiny.kv_skew",
       "kv_write_host_ms.kv": "tiny.kv_skew"}


@pytest.fixture
def tiny_with_program_spans(tmp_path):
    """The tiny fixture with the repo's entries for the new metrics,
    pointed at the tiny cells; every kv read is followed by a write, so
    the stretch holds writes."""
    root = tmp_path / "tiny"
    shutil.copytree(TINY, root)
    b = load_benchmark(root)
    entries = {m["name"]: m for m in load_benchmark(ROOT)["per_layer"]}
    b["per_layer"] += [dict(entries[name], workloads=[cell])
                       for name, cell in NEW.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    traffic = root / "bench" / "traffic" / "tiny_kv_skew.json"
    t = json.loads(traffic.read_text())
    t["write_prob"] = 1.0
    traffic.write_text(json.dumps(t))
    return root


@pytest.mark.parametrize("cell", sorted(set(NEW.values())))
def test_traced_tiny_cell_reports_program_metrics(run_tiny, cell,
                                                  tiny_with_program_spans):
    r = run_tiny(cell, trace=True, root=tiny_with_program_spans)
    assert r["correct"]
    want = {name for name, c in NEW.items() if c == cell}
    got = {k: v["value"] for k, v in r["metrics"].items() if k in want}
    assert set(got) == want
    assert all(v > 0 for v in got.values()), got
    if cell == "tiny.kv_skew":
        assert r["metrics"]["kv_h2d_mib_per_read.kv"]["unit"] == "MiB"


@pytest.mark.parametrize("name", sorted(NEW))
def test_untraced_reads_none(name):
    reader = load_module("metrics", name, ROOT)
    assert reader.read(types.SimpleNamespace(trace=None)) is None
