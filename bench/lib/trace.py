"""Profiler trace of a run's window, and its reduction to numbers.

The driver wraps each call it makes into a layer in a host span of its
own (`bench/engine_run`, `bench/read_pages`, ...), with
`jax.profiler.TraceAnnotation`, so host spans and device operations share
the profiler's clock.  The traced stretch of the window is the span
`bench/traced` (`Stretch`).

Reduction:
  * busy: the union of the intervals of device operations (the "XLA Ops"
    line of each device plane) within the stretch, averaged over devices;
    idle share is 1 - busy / window;
  * a module's or kernel's device time: the summed durations of its
    events on the "XLA Modules" or "XLA Ops" line;
  * idle gaps: the holes in the busy union, each labelled by the
    innermost benchmark host span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import pathlib
import re
import shutil
import time

HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
TRACED_SPAN = "bench/traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceData:
    """Intervals (start_ns, end_ns, name) read from one trace."""
    ops: dict          # device index -> [(s, e, name)] on the ops line
    modules: dict      # device index -> [(s, e, name)] on the modules line
    spans: list        # [(s, e, name)] benchmark host spans

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "TraceData":
        def tup(rows):
            return [(int(s), int(e), str(n)) for s, e, n in rows]
        return cls(ops={int(k): tup(v) for k, v in d["ops"].items()},
                   modules={int(k): tup(v) for k, v in d["modules"].items()},
                   spans=tup(d["spans"]))


class Capture:
    """jax.profiler trace into a fixed directory, emptied first."""

    def __init__(self, out_dir: pathlib.Path):
        self.out_dir = pathlib.Path(out_dir)

    def start(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.out_dir))

    def stop(self) -> pathlib.Path:
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.out_dir}")
        return pathlib.Path(found[-1])


class Stretch:
    """The traced part of a full-length window.

    A traced run drives the same window as a timed one; the profiler
    covers a stretch of it, set by the traffic file's `trace` entry.  The
    driver calls `open` as its window starts, `poll` wherever the profiler
    may start or stop without splitting the work it measures (a wave
    boundary, between two reads), and `close` as its window ends.  The
    profiler starts at the first such point `from_s` into the window and
    stops at the first one `seconds` after it started, or at `close`.
    The host span `bench/traced` marks the stretch in the trace; `a` and
    `b` are its ends on the host clock, and `counters` the driver's
    counters (`snapshot()`) at each end.
    """

    def __init__(self, capture: Capture, from_s: float, seconds: float,
                 snapshot=dict):
        self.capture, self.from_s, self.seconds = capture, from_s, seconds
        self.snapshot = snapshot
        self.t0 = self.a = self.b = self.path = None
        self.counters: tuple = ()
        self._span = None

    def open(self, t0: float):
        self.t0 = t0

    def poll(self):
        now = time.perf_counter()
        if self.a is None:
            if now - self.t0 >= self.from_s:
                self._start()
        elif self.b is None and now - self.a >= self.seconds:
            self._stop()

    def close(self):
        if self.a is None:
            raise ValueError(f"the window ended before its traced stretch "
                             f"began ({self.from_s} s into it)")
        if self.b is None:
            self._stop()

    def _start(self):
        import jax
        self.capture.start()
        self._span = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()
        self.counters = (self.snapshot(),)
        self.a = time.perf_counter()

    def _stop(self):
        self.b = time.perf_counter()
        self.counters += (self.snapshot(),)
        self._span.__exit__(None, None, None)
        self.path = self.capture.stop()


_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)")


def load_xplane(path) -> TraceData:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                rows = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name) for e in line.events]
                (ops if line.name == OPS_LINE else modules).setdefault(
                    dev, []).extend(rows)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      e.name))
    return TraceData(ops=ops, modules=modules, spans=spans)


_OP_NAME = re.compile(r"^%?([\w.\-]+)(?: = (\w+\[[\d,]*\]))?")
# control flow whose events span the operations of its body
_CONTAINERS = re.compile(r"^%?(while|conditional|call)\b")


def op_label(text: str) -> str:
    """An operation's short name: the instruction and its result shape
    (`fusion.137 bf16[16,1,32,80]`), not the whole HLO line."""
    m = _OP_NAME.match(text)
    if not m:
        return text[:80]
    return " ".join(g for g in m.groups() if g)


def merge(intervals):
    """Union of (s, e[, ...]) intervals as sorted disjoint (s, e)."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo, hi) -> int:
    """Nanoseconds of [lo, hi) that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def holes(merged, lo, hi):
    """The parts of [lo, hi) that the merged intervals leave uncovered."""
    out, cur = [], lo
    for s, e in merged:
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


@dataclasses.dataclass
class Reduction:
    data: TraceData
    lo: int
    hi: int
    busy: dict          # device -> merged busy intervals

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over devices."""
        if not self.busy:
            return 0.0
        tot = sum(covered(m, self.lo, self.hi) for m in self.busy.values())
        return tot / len(self.busy) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _events(self, line: dict, pattern: str):
        rx = re.compile(pattern)
        return [(s, e, n) for rows in line.values() for s, e, n in rows
                if rx.search(n) and s >= self.lo and e <= self.hi]

    def module_time(self, pattern: str):
        """(seconds, count) of the module events matching `pattern` that
        lie wholly within the window, summed over devices."""
        ev = self._events(self.data.modules, pattern)
        return sum(e - s for s, e, _ in ev) * 1e-9, len(ev)

    def op_time(self, pattern: str):
        """(seconds, count) of the op events matching `pattern`."""
        ev = self._events(self.data.ops, pattern)
        return sum(e - s for s, e, _ in ev) * 1e-9, len(ev)

    def spans(self, name: str):
        return [(s, e) for s, e, n in self.data.spans
                if n == name and s >= self.lo and e <= self.hi]

    def idle_within(self, lo: int, hi: int) -> float:
        """Seconds of [lo, hi) in which the device is idle, averaged over
        devices."""
        if not self.busy:
            return (hi - lo) * 1e-9
        idle = [(hi - lo) - covered(m, lo, hi) for m in self.busy.values()]
        return sum(idle) / len(idle) * 1e-9

    def label(self, t: int) -> str:
        """The innermost benchmark span covering time t."""
        best = None
        for s, e, n in self.data.spans:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "outside-spans"

    def breakdown(self, top: int = 10) -> dict:
        """Device operations that took most time (loops and calls left
        out: their bodies' operations are counted), and idle time grouped
        by the host span that covers it, in seconds, the largest first."""
        ops: dict = {}
        for rows in self.data.ops.values():
            for s, e, n in rows:
                if s >= self.lo and e <= self.hi and \
                        not _CONTAINERS.match(n):
                    lab = op_label(n)
                    ops[lab] = ops.get(lab, 0) + (e - s)
        n_dev = max(len(self.busy), 1)
        device_ops = sorted(([n, t * 1e-9 / n_dev] for n, t in ops.items()),
                            key=lambda r: -r[1])[:top]
        idle: dict = {}
        for m in self.busy.values():
            for s, e in holes(m, self.lo, self.hi):
                lab = self.label((s + e) // 2)
                idle[lab] = idle.get(lab, 0) + (e - s)
        idle_gaps = sorted(([n, t * 1e-9 / n_dev] for n, t in idle.items()),
                           key=lambda r: -r[1])[:top]
        return {"device_ops": device_ops, "idle_gaps": idle_gaps}


def reduce(data: TraceData, window_span: str = WINDOW_SPAN) -> Reduction:
    wins = [(s, e) for s, e, n in data.spans if n == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = wins[0]
    busy = {dev: merge(rows) for dev, rows in data.ops.items()}
    return Reduction(data=data, lo=lo, hi=hi, busy=busy)


def save_fixture(data: TraceData, path, lo: int | None = None,
                 hi: int | None = None):
    """Write a (possibly clipped) trace as JSON, for tests."""
    def clip(rows):
        return [r for r in rows if (lo is None or r[0] >= lo)
                and (hi is None or r[1] <= hi)]
    d = TraceData(ops={k: clip(v) for k, v in data.ops.items()},
                  modules={k: clip(v) for k, v in data.modules.items()},
                  spans=clip(data.spans))
    pathlib.Path(path).write_text(json.dumps(d.to_json()))
