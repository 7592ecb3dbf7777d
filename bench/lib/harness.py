"""One run of one cell: set up, measure a window, read the metrics, check
the outputs against the reference.

The driver named by the cell's traffic file does the set-up, the window
and the check; the harness times set-up, traces a stretch of the window
when asked, reads each metric with its own reader, and builds the result
line.  A traced run drives the same full-length window as a timed one;
its per-layer metrics are read over the stretch the traffic file's
`trace` entry sets (`trace.Stretch`).
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time

from . import device, models
from .cell import Cell, load_module
from .trace import TRACED_SPAN, Capture, Reduction, Stretch, load_xplane, \
    reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: Cell
    seed: int
    spec: models.Spec
    peaks: dict | None
    setup_s: float
    record: dict                # the driver's record of the window, or of
    trace: Reduction | None     # its traced stretch

    @property
    def samples(self) -> dict:
        return self.record["samples"]

    @property
    def counters(self) -> dict:
        return self.record["counters"]


class _Compiles:
    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


def _read_metrics(run: Run, entries: list) -> dict:
    out = {}
    for m in entries:
        reader = load_module("metrics", m["name"], run.cell.root)
        v = reader.read(run)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def compare(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every number with a
    limit; a number the check did not produce fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = float(values.get(name, math.inf))
        if not math.isfinite(v):
            v = 1e30            # no reading: far above any limit, in JSON
        checks[name] = {"value": v, "limit": limit}
        ok &= v <= limit
    return ok, checks


def use_compile_cache():
    """The checkout's persistent compilation cache, keeping every
    program, however quick to compile, for the next run."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def control_cell(cell: Cell, seed: int, seconds: float,
                 compile_cache: bool = True) -> dict:
    """The control's readings on one seed: the cell's traffic for a
    window, then the reference in the program's place one precision
    down, read by the same comparison (`Driver.control`).  Also the
    program's own readings of that window, for the side by side."""
    if compile_cache:
        use_compile_cache()
    drv = load_module("drivers", cell.traffic["driver"], cell.root).Driver(
        cell, seed)
    drv.setup()
    drv.window(seconds)
    drv.release()
    return {"program": drv.check(), "control": drv.control()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs=None, after_setup=None,
             out_dir=None, compile_cache: bool = True) -> dict:
    """The result line's object.  `devs` are the devices measured (None:
    the CPU, for tests: no peaks, no memory reading)."""
    if compile_cache:
        use_compile_cache()
    compiles = _Compiles()
    peaks = device.peaks(devs[0].device_kind) if devs else None
    drv = load_module("drivers", cell.traffic["driver"], cell.root).Driver(
        cell, seed)
    drv.setup()
    if after_setup is not None:
        after_setup(drv)
    stretch = None
    if trace:
        cap = Capture((out_dir or cell.root / "bench" / "out")
                      / "trace" / cell.name)
        t = cell.traffic["trace"]
        stretch = Stretch(cap, t["from_s"], t["seconds"], drv.counters)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    n_compiles = compiles.n
    record = drv.window(seconds, stretch)
    window_compiles = compiles.n - n_compiles
    red = None
    if stretch is not None:
        red = reduce(load_xplane(stretch.path), TRACED_SPAN)
    mem = device.memory_peak_bytes(devs) if devs else 0
    run = Run(cell=cell, seed=seed, spec=models.spec(cell.config),
              peaks=peaks, setup_s=setup_s,
              record=drv.stretch_record(stretch) if stretch else record,
              trace=red)
    metrics = _read_metrics(run, cell.per_layer if trace else cell.end_to_end)
    drv.release()
    values = drv.check()
    limits = cell.limits()
    correct, checks = compare(values, {k: limits[k] for k in drv.CHECKS})
    dev = device.describe(devs) if devs else {"platform": "cpu",
                                               "kind": "cpu", "count": 1}
    dev["memory_peak_bytes"] = mem
    result = {"correct": bool(correct), "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics, "device": dev}
    if red is not None:
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    info = {k: v for k, v in values.items() if k not in checks}
    counts = {k: v for k, v in record["counters"].items()
              if not isinstance(v, list)}
    traced = ""
    if stretch is not None:
        c = {k: v for k, v in run.counters.items() if not isinstance(v, list)}
        traced = (f", traced {stretch.a - drv.t0:.3f}-"
                  f"{stretch.b - drv.t0:.3f} s (counters {json.dumps(c)})")
    print(f"[run] {cell.name} seed {seed}: set-up {setup_s:.3f} s, window "
          f"{record['seconds']:.3f} s{traced}, {window_compiles} compiles "
          f"in the window; {json.dumps(info)}; window counters "
          f"{json.dumps(counts)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    result["checks"] = checks
    return result
