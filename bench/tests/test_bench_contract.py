"""BENCHMARK.json: names and units in the allowed characters, every key
as the benchmark's format fixes it, and every file a cell names found by
name; the command refuses to run off a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench.lib.cell import load_benchmark, load_cell, load_module
from bench.tests.conftest import ROOT, TINY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(params=["repo", "tiny"])
def bench(request):
    root = ROOT if request.param == "repo" else TINY
    return root, load_benchmark(root)


def test_top_level_keys(bench):
    _, b = bench
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51


def test_names_units_and_lines(bench):
    _, b = bench
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert LINE.match(c["source"]) and LINE.match(c["why"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"])
        names.append(w["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if "bound" in m else {"layer", "moves"}
        assert set(m) <= allowed
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_bounds_and_setup_metric(bench):
    _, b = bench
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_finds_its_files_by_name(bench):
    root, b = bench
    for w in b["workloads"]:
        cell = load_cell(w["name"], root)
        load_module("drivers", cell.traffic["driver"], root)
        load_module("traffic", cell.traffic["generator"], root)
        load_module("reference", cell.config["reference"], root)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(load_module("metrics", m["name"], root).read)
        for m in cell.per_layer:
            assert m["moves"] in names
        limits = cell.limits()
        drv = load_module("drivers", cell.traffic["driver"], root)
        assert set(drv.Driver.CHECKS) <= set(limits)


def test_configs_state_their_cut(bench):
    root, b = bench
    for c in b["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg
        # the program's departures from the published model are stated
        # apart, each with the value served, and never as a cut
        for key, d in cfg.get("departures", {}).items():
            assert set(d) == {"served", "why"} and key not in c["reduced"]


def test_spec_serves_the_departures():
    """The file holds the published values; the references and the
    program are built with the served ones."""
    from bench.lib import models
    cfg = json.loads((ROOT / "bench" / "configs" / "stablelm-3b.json")
                     .read_text())
    assert (cfg["norm_eps"], cfg["rope_pct"]) == (1e-05, 0.25)
    assert models.spec(cfg).norm_eps == 1e-06
    cfg["departures"].pop("rope_pct")
    with pytest.raises(ValueError, match="rotate every head dimension"):
        models.spec(cfg)


def test_off_a_tpu_no_result_and_nonzero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "stablelm3b.batch", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr
