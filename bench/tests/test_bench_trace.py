"""The reduction from a profiler trace to busy time, idle share, module
and kernel times, and labelled idle gaps."""
import json

import pytest

from bench.lib import trace
from bench.tests.conftest import ROOT

FIXTURES = ROOT / "bench" / "tests" / "fixtures"


def small():
    """A hand-made trace: one device, a window of 100 ns."""
    return trace.TraceData(
        ops={0: [(10, 30, "fusion.1"), (20, 40, "fusion.2"),
                 (60, 70, "ralt_kernel"), (95, 120, "copy.1"),
                 (150, 160, "late")]},
        modules={0: [(10, 40, "jit_decode_step(7)"),
                     (60, 70, "jit__lambda(9)"),
                     (95, 120, "jit_decode_step(7)")]},
        spans=[(0, 100, "bench/window"), (45, 80, "bench/read_pages"),
               (82, 90, "bench/write_page")])


def test_merge_and_cover():
    m = trace.merge([(10, 30), (20, 40), (60, 70), (70, 75)])
    assert m == [(10, 40), (60, 75)]
    assert trace.covered(m, 0, 100) == 45
    assert trace.covered(m, 35, 65) == 10
    assert trace.holes(m, 0, 100) == [(0, 10), (40, 60), (75, 100)]


def test_busy_and_idle_share():
    red = trace.reduce(small())
    # busy in [0, 100): [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(45e-9)
    assert red.idle_share == pytest.approx(0.55)


def test_module_and_kernel_times_inside_the_window():
    red = trace.reduce(small())
    t, n = red.module_time(r"^jit_decode_step\b")
    assert n == 1 and t == pytest.approx(30e-9)     # the second ends late
    t, n = red.op_time("ralt")
    assert n == 1 and t == pytest.approx(10e-9)


def test_idle_within_a_span():
    red = trace.reduce(small())
    # read_pages [45, 80): busy [60, 70) -> idle 25 ns
    assert red.idle_within(45, 80) == pytest.approx(25e-9)


def test_gap_labels_are_the_innermost_span():
    red = trace.reduce(small())
    assert red.label(50) == "bench/read_pages"
    assert red.label(85) == "bench/write_page"
    assert red.label(5) == "bench/window"
    b = red.breakdown()
    gaps = dict(b["idle_gaps"])
    # holes: [0,10) window, [40,60) read_pages, [70,95) midpoint 82 ->
    # write_page
    assert gaps["bench/window"] == pytest.approx(10e-9)
    assert gaps["bench/read_pages"] == pytest.approx(20e-9)
    assert gaps["bench/write_page"] == pytest.approx(25e-9)
    ops = dict(b["device_ops"])
    assert "late" not in ops and ops["fusion.2"] == pytest.approx(20e-9)


def test_fixture_round_trip(tmp_path):
    path = tmp_path / "t.json"
    trace.save_fixture(small(), path)
    again = trace.TraceData.from_json(json.loads(path.read_text()))
    assert again == small()


def test_no_window_span_is_an_error():
    d = small()
    d.spans = [s for s in d.spans if s[2] != "bench/window"]
    with pytest.raises(ValueError):
        trace.reduce(d)


def recorded(name):
    return trace.TraceData.from_json(json.loads(
        (FIXTURES / f"recorded_{name}.json").read_text()))


def test_recorded_serving_trace():
    """120 ms of a stablelm-3b batch window on one v5e (first chip run of
    this benchmark): the decode step runs back to back."""
    red = trace.reduce(recorded("stablelm3b.batch"))
    assert red.window_s == pytest.approx(0.120)
    assert 0.9 < red.busy_s / red.window_s < 1.0
    t, n = red.module_time(r"^jit_decode_step\b")
    assert n == 2 and 0.040 < t / n < 0.055
    b = red.breakdown()
    assert len(b["device_ops"]) == 10
    assert not any(name.startswith("while") for name, _ in b["device_ops"])
    assert {lab for lab, _ in b["idle_gaps"]} <= {"bench/engine_run",
                                                  "bench/window"}
    # busy is a union: nested and overlapping events are not summed twice
    summed = sum(e - s for rows in red.data.ops.values() for s, e, _ in rows
                 if s >= red.lo and e <= red.hi) * 1e-9
    assert summed > red.busy_s


def test_recorded_tier_trace():
    """150 ms of a tiered-KV window: the RALT kernel's events and the read
    spans are found by name."""
    red = trace.reduce(recorded("stablelm3b.kv_skew"))
    t, n = red.op_time(r"^%?ralt_update\b")
    assert n == 5 and 0 < t / n < 2e-6
    reads = red.spans("bench/read_pages")
    assert len(reads) == 6
    idle = [red.idle_within(s, e) for s, e in reads]
    assert all(0 <= i <= (e - s) * 1e-9 for i, (s, e) in zip(idle, reads))
    assert 0 < red.idle_share < 1


def test_op_labels_are_short():
    text = ("%copy.37 = bf16[32,16,32,512,80]{3,4,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[32,16,32,512,80]{3,4,2,1,0} %x)")
    assert trace.op_label(text) == "copy.37 bf16[32,16,32,512,80]"
    assert trace.op_label("%ralt_update.1 = (s32[16,128], f32[16,128]) "
                          "custom-call(...)") == "ralt_update.1"


def test_reduction_over_the_traced_stretch():
    """A traced run reduces over its `bench/traced` span, not the whole
    window."""
    d = small()
    d.spans.append((50, 100, trace.TRACED_SPAN))
    red = trace.reduce(d, trace.TRACED_SPAN)
    assert (red.lo, red.hi) == (50, 100)
    assert red.busy_s == pytest.approx(15e-9)       # [60,70) and [95,100)
    assert red.module_time(r"^jit_decode_step\b") == (0.0, 0)


def test_kv_mfu_counts_each_reads_least_time():
    """step_mfu.kv: per read, the larger of the RALT update's operations
    at the bf16 peak and its bytes plus the pages it returns at the HBM
    peak, over the stretch."""
    import types
    from bench.lib import flops, readers
    d = small()                             # one read span in [0, 100)
    red = trace.reduce(d)
    peaks = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12}
    c = {"n_units": 1000, "pages_per_read": 11, "page_bytes": 5 << 20}
    run = types.SimpleNamespace(trace=red, peaks=peaks, counters=c)
    f, b = flops.ralt_update_cost(1000)
    want = max(f / 1e15, (b + 11 * (5 << 20)) / 1e12) / 100e-9 * 100
    assert readers.kv_mfu_pct(run) == pytest.approx(want)
    run.trace = None
    assert readers.kv_mfu_pct(run) is None
