"""Whole runs of the tiny fixture cells on the CPU: the window's
arithmetic, traced runs, and a cell defined only in new files."""
import json
import shutil

import pytest

from bench.tests.conftest import TINY

SERVING = ["tiny.batch", "tiny.chat"]
KV = ["tiny.kv_skew", "tiny.kv_uniform"]


def test_batch_window_holds_whole_waves(tiny_cell):
    from bench.lib.harness import load_module
    cell = tiny_cell("tiny.batch")
    drv = load_module("drivers", "serve", cell.root).Driver(cell, 11)
    drv.setup()
    rec = drv.window(0.3)
    admitted = drv.requests
    assert admitted and all(r.done for r in admitted)
    # every admitted request finished inside the window, and the window
    # closed right after the last token: it ends on a wave boundary
    last = max(t for r in admitted for t in r.out.times)
    assert drv.t1 >= last and drv.t1 - last < 0.05
    assert rec["seconds"] >= 0.3
    assert rec["counters"]["tokens"] == sum(len(r.out) for r in admitted)
    assert rec["failed"] == 0
    # every wave holds the same sizes: whole waves of `batch` requests
    assert len(admitted) % cell.config["serving"]["batch"] == 0


def test_tokens_per_s_is_tokens_over_the_window(run_tiny):
    r = run_tiny("tiny.batch", seconds=0.4)
    assert r["correct"]
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert list(r)[-1] == "checks"


def test_open_loop_counts_unserved_requests(tiny_cell):
    """A request due in the window and still not served when the drain
    is cut is attempted, failed, and ranks at the top of the time to
    first token."""
    from bench.lib.harness import load_module
    cell = tiny_cell("tiny.chat")
    drv = load_module("drivers", "serve", cell.root).Driver(cell, 12)
    drv.setup()
    orig = drv.engine.run
    calls = []

    def one_run_only(**kw):         # the engine serves one call, then stalls
        calls.append(1)
        return orig(**kw) if len(calls) == 1 else None

    drv.engine.run = one_run_only
    rec = drv.window(0.5)
    waiting = drv.engine.queue
    assert rec["attempted"] == len(drv.requests)
    assert rec["failed"] >= list.__len__(waiting) > 0
    assert len(rec["samples"]["ttft_missing_s"]) == rec["failed"]


def test_open_loop_drains_every_request_due(tiny_cell):
    """Arrivals stop at the close; every request due by then is served
    after it, so each has a real first-token time."""
    from bench.lib.harness import load_module
    cell = tiny_cell("tiny.chat")
    drv = load_module("drivers", "serve", cell.root).Driver(cell, 14)
    drv.setup()
    rec = drv.window(0.3)
    assert rec["attempted"] == len(drv.requests) > 0
    assert all(r.due < drv.t0 + 0.3 for r in drv.requests)
    assert all(r.done for r in drv.requests)
    assert rec["failed"] == 0 and not rec["samples"]["ttft_missing_s"]
    assert len(rec["samples"]["ttft_s"]) == rec["attempted"]
    last = max(t for r in drv.requests for t in r.out.times)
    assert drv.t1 >= last


class _NoProfiler:
    """A capture that records nothing: the stretch's bookkeeping alone."""

    def start(self):
        pass

    def stop(self):
        return None


def _stretch(cell, drv):
    from bench.lib.trace import Stretch
    t = cell.traffic["trace"]
    return Stretch(_NoProfiler(), t["from_s"], t["seconds"], drv.counters)


@pytest.mark.parametrize("name", SERVING)
def test_traced_stretch_holds_whole_waves(tiny_cell, name):
    """The stretch starts and stops at wave boundaries, after `from_s`,
    and its record counts the steps of the waves admitted inside it."""
    from bench.lib.harness import load_module
    cell = tiny_cell(name)
    drv = load_module("drivers", "serve", cell.root).Driver(cell, 15)
    drv.setup()
    st = _stretch(cell, drv)
    rec = drv.window(0.5, st)
    assert st.a - drv.t0 >= cell.traffic["trace"]["from_s"]
    assert st.a < st.b <= drv.t1
    admitted = [r for r in drv.requests if r.admitted is not None]
    # it ends with its last wave, or while the engine idles once it is due
    last = max(r.out.times[-1] for r in admitted if st.a <= r.admitted < st.b)
    assert 0 <= st.b - max(last, st.a + st.seconds) < 0.1
    # the waves' steps add up to the engine's own count
    assert drv.wave_steps(admitted) == rec["counters"]["steps"]
    inside = {r.wave for r in admitted if st.a <= r.admitted < st.b}
    assert inside
    for r in admitted:              # no wave is split by the stretch
        assert (r.wave in inside) == (st.a <= r.admitted < st.b)
    tr = drv.stretch_record(st)
    assert tr["counters"]["steps"] == drv.wave_steps(
        [r for r in admitted if r.wave in inside])
    assert tr["seconds"] == st.b - st.a
    assert 0 < tr["counters"]["useful_token_steps"] <= \
        tr["counters"]["steps"] * tr["counters"]["batch"]


def test_kv_stretch_counts_its_own_reads(tiny_cell):
    """The kv stretch holds the reads started inside it, and the change
    of the tier manager's counters across it: one hit per page read."""
    from bench.lib.harness import load_module
    cell = tiny_cell("tiny.kv_skew")
    drv = load_module("drivers", "kv", cell.root).Driver(cell, 16)
    drv.setup()
    st = _stretch(cell, drv)
    rec = drv.window(0.4, st)
    tr = drv.stretch_record(st)
    c = tr["counters"]
    assert 0 < c["reads"] < rec["counters"]["reads"]
    assert c["fast_hits"] + c["slow_hits"] == c["reads"] * c["pages_per_read"]
    assert c["page_bytes"] == drv.contents.base[0].nbytes


def test_kv_writes_are_timed_apart_from_reads(tiny_cell):
    """Each rewrite is timed on its own, until the pool it changed is
    ready, so no read's time holds the write before it."""
    from bench.lib.harness import load_module
    cell = tiny_cell("tiny.kv_skew")
    cell.traffic["write_prob"] = 1.0
    drv = load_module("drivers", "kv", cell.root).Driver(cell, 13)
    drv.setup()
    rec = drv.window(0.3)
    reads, writes = rec["samples"]["read_s"], rec["samples"]["write_s"]
    assert len(writes) == len(reads) > 0
    assert all(w > 0 for w in writes)


@pytest.mark.parametrize("name", SERVING + KV)
def test_cell_runs_correct(run_tiny, name):
    r = run_tiny(name, seed=2**33 + 17)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    assert r["failed"] == 0         # the open loop drains what is due


@pytest.mark.parametrize("name", SERVING + KV)
def test_traced_run_reports_per_layer_metrics(run_tiny, name):
    r = run_tiny(name, trace=True)
    assert r["correct"]
    assert "busy_s" in r["device"] and "window_s" in r["device"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


# ----------------------------------------------- a cell of new files only
def test_cell_defined_only_in_new_files(tmp_path, run_tiny):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as files plus BENCHMARK.json entries, editing nothing."""
    root = tmp_path / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    shutil.copy(TINY / "bench" / "configs" / "stablelm-tiny.json",
                root / "bench" / "configs" / "other-tiny.json")
    t = json.loads((TINY / "bench" / "traffic" / "tiny_batch.json")
                   .read_text())
    t["prompt"] = dict(t["prompt"], median=4, max=8)
    (root / "bench" / "traffic" / "short.json").write_text(json.dumps(t))
    (root / "bench" / "metrics" / "waves_seen.tps.py").write_text(
        "def read(run):\n"
        "    return run.counters['steps'] / 1.0\n")
    b = json.loads((TINY / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench" / "configs" / "other-tiny.json")
                     .read_text())
    b["configs"] = [{"name": "other-tiny", "source": cfg["source"],
                     "file": "bench/configs/other-tiny.json",
                     "reduced": cfg["reduced"], "why": "test"}]
    b["workloads"] = [{"name": "other.short", "config": "other-tiny",
                       "traffic": "short", "chips": 1, "why": "test"}]
    b["end_to_end"] = [dict(m, workloads=["other.short"])
                       for m in b["end_to_end"] if m["name"] == "tokens_per_s"
                       ] + [m for m in b["end_to_end"]
                            if m["name"] == "setup_s"]
    b["per_layer"] = [{"name": "waves_seen.tps", "unit": "steps",
                       "better": "lower", "source": "program_counter",
                       "layer": "engine", "moves": "tokens_per_s",
                       "workloads": ["other.short"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    r = run_tiny("other.short", root=root)
    assert r["correct"]
    assert {"tokens_per_s", "setup_s"} <= set(r["metrics"])
    r = run_tiny("other.short", root=root, trace=True)
    assert r["metrics"]["waves_seen.tps"]["value"] > 0
