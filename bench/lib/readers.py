"""Arithmetic the metric readers share.  Each metric has its own reader
in bench/metrics/<name>.py; a reader that finds nothing to read returns
None and the metric is left out of the result line."""
from __future__ import annotations

from . import flops, stats

MS = 1e3
PCT = 100.0


def p95_ms(values):
    return stats.percentile(values, 95) * MS if values else None


def idle_share_pct(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return run.trace.idle_share * PCT


def module_ms_per_call(run, pattern: str):
    if run.trace is None:
        return None
    total, count = run.trace.module_time(pattern)
    return total / count * MS if count else None


def serving_mfu_pct(run):
    """Operations the model needs for every token the traced stretch's
    waves were fed, over the stretch at the chip's bf16 peak."""
    if run.trace is None or run.peaks is None:
        return None
    seqs = run.counters["sequences"]
    if not seqs:
        return None
    need = sum(flops.sequence_flops(run.spec, n) for n in seqs)
    return need / (run.trace.window_s * run.peaks["bf16_flops_per_s"]) * PCT


def slot_occupancy_pct(run):
    c = run.counters
    if not c["steps"]:
        return None
    return c["useful_token_steps"] / (c["steps"] * c["batch"]) * PCT


# The RALT kernel's events in the device trace: its custom call is named
# after the Pallas kernel (`%ralt_update.1 = (...) custom-call(...)`).
RALT_KERNEL = r"^%?ralt_update\b"


def ralt_roofline_pct(run):
    if run.trace is None or run.peaks is None:
        return None
    total, count = run.trace.op_time(RALT_KERNEL)
    if not count or total <= 0:
        return None
    f, b = flops.ralt_update_cost(run.counters["n_units"])
    least = flops.roofline_seconds(f, b, run.peaks)
    return least * count / total * PCT


def kv_mfu_pct(run):
    """The tier path's share of the chip's peak: for every read of the
    stretch, the least time its work needs on the chip (each page it
    returns read once from HBM, and the RALT update over the tracked
    units; the larger of operations over the bf16 peak and bytes over the
    HBM peak), over the traced stretch."""
    if run.trace is None or run.peaks is None:
        return None
    reads = len(run.trace.spans("bench/read_pages"))
    if not reads:
        return None
    c = run.counters
    f, b = flops.ralt_update_cost(c["n_units"])
    b += c["pages_per_read"] * c["page_bytes"]
    least = flops.roofline_seconds(f, b, run.peaks)
    return reads * least / run.trace.window_s * PCT


def host_ms_per_read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans("bench/read_pages")
    if not spans:
        return None
    return sum(run.trace.idle_within(s, e) for s, e in spans) \
        / len(spans) * MS
