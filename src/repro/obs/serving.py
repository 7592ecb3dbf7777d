"""Observability for the JAX serving half: the same compiled-out
plane as the numpy core, attached to the tiering components
(`TieredKVCache`, `ExpertCache`, `TieredEmbedding`), their RALT
trackers (`HotTracker`) and the `ServeEngine`.

Clock
-----
Spans are stamped with `time.time_ns`, the host clock `jax.profiler`
stamps its own events with, so the plane's log and a profiler trace of
the same stretch share one time line.  While a profiler session
records, every span is also entered as a `jax.profiler.TraceAnnotation`
of the same name: it then sits in the `.xplane.pb` beside the device
operations.  The in-process log is what the benchmark's readers read.

Gate
----
Every top-level call of a component (an engine step or wave, a
`read_pages`, a `write_page`, a lookup, a routing step) asks its plane
once, ``obs.poll()``, whether to record; the sites nested inside it
reuse that answer through one attribute check (``if obs.enabled:``).
The class-level default, `DEFAULT_SERVING_OBS`, records exactly while a
profiler session records (`TraceAnnotation.is_enabled()`), clears its
log when it sees a new session begin, and records `host/gc` spans from
a `gc.callbacks` hook meanwhile; after `stop_trace` its log is that
session's.  With no session a site costs one attribute load and a
branch, and a top-level call one `is_enabled()` call.  An explicitly
attached ``ServingObservability()`` records always, with or without a
profiler (tests, `chip_smoke.py`, `benchmarks/tiered_serving.py`).

The plane reads the components' counters and pool aggregates but never
charges HBM/PCIe time or mutates a page table: the stats-discipline
lint (`tools/check/stats_discipline.py`) enforces the same read-only
rule over this module as over the core plane.

Two legs:

  * `Tracer` (shared class): spans for engine steps and their parts,
    page reads and writes and their parts, RALT tracker calls, eviction
    sweeps, bulk staging flushes, expert rebalances; instants for the
    page-level pathways (`page/retained`, `page/promo_compaction`,
    `page/promo_flush`, `page/promo_abort`) and the engine's request
    timestamps; counter lanes (`pool`, `bytes`) at the end of each
    sweep and flush.  The taxonomy is in `docs/OBSERVABILITY.md`.
  * `TokenAttributionSampler` — reservoir-sampled per-token records
    (component kind, pages gathered, pages fetched from host, simulated
    cost, behind-sweep flag) feeding the "why slow" table printed by
    `benchmarks/tiered_serving.py`.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from .trace import Tracer

__all__ = ["ServingObservability", "TokenAttributionSampler",
           "DEFAULT_SERVING_OBS", "KIND_NAMES", "component_sample"]

KIND_NAMES = ("kv", "emb", "expert", "engine")
KIND_CODES = {name: i for i, name in enumerate(KIND_NAMES)}
POOL_LANE = ("hbm_occupancy", "staging_depth", "page_hit_rate")
BYTES_LANE = ("h2d_bytes", "d2h_bytes")


def _is_profiling() -> bool:
    """`jax.profiler.TraceAnnotation.is_enabled()`: whether a profiler
    session records.  JAX is imported on the first call, not with this
    module (the numpy half imports `repro.obs`); the call then rebinds
    this name to the profiler's own function."""
    global _is_profiling, _Annotation, _profile_state
    from jax._src import profiler
    from jax.profiler import TraceAnnotation
    _Annotation = TraceAnnotation
    _profile_state = getattr(profiler, "_profile_state", None)
    _is_profiling = TraceAnnotation.is_enabled
    return _is_profiling()


_Annotation = None      # jax.profiler.TraceAnnotation, once looked up
_profile_state = None   # where jax.profiler keeps its session, likewise


def _profiler_session():
    """The object of the `jax.profiler` session in progress (None where
    JAX keeps none, as for a capture from a profiler server): a new
    object means a new session."""
    return getattr(_profile_state, "profile_session", None)


def _fast_capacity(comp) -> int:
    cfg = getattr(comp, "cfg", None)
    if cfg is not None and hasattr(cfg, "fast_slots"):
        return cfg.fast_slots
    for attr in ("fast_rows", "fast_experts"):
        c = getattr(comp, attr, None)
        if c is not None:
            return int(c)
    return 0


def component_sample(comp) -> dict[str, float]:
    """One read-only sample of a tiering component's aggregates.
    Everything here is a read of public counters — no charge APIs, no
    page-table writes (the lint enforces it)."""
    out: dict[str, float] = {}
    clock = getattr(comp, "clock", None)
    if clock is None:
        return out
    hits = clock.fast_hits + clock.slow_hits
    out["page_hit_rate"] = clock.fast_hits / hits if hits else 0.0
    for k in BYTES_LANE:
        v = getattr(comp, k, None)
        if v is not None:
            out[k] = float(v)
    cap = _fast_capacity(comp)
    free = getattr(comp, "free_slots", None)
    if free is None:
        free = getattr(comp, "free", None)
    if cap and free is not None:
        out["hbm_occupancy"] = (cap - len(free)) / cap
    staging = getattr(comp, "staging", None)
    if staging is not None:
        out["staging_depth"] = float(len(staging))
    return out


class _ProfilerClockTracer(Tracer):
    """Stamps events with `time.time_ns`, the host clock `jax.profiler`
    stamps its own with."""

    us_per_tick = 1e-3


class TokenAttributionSampler:
    """Bounded reservoir (Algorithm R) of per-token gather records.

    One record per data-plane access (a KV page gather, an embedding
    lookup, an expert-routing step): which component kind served it,
    how many units were gathered and how many came from the host tier,
    the simulated cost of the access, and whether it landed behind a
    maintenance pass (eviction sweep / staging flush / rebalance) that
    ran inside the same access."""

    def __init__(self, capacity: int = 65536, seed: int = 0):
        self.capacity = capacity
        self._rng = np.random.default_rng(seed)
        self.lat = np.zeros(capacity)
        self.kind = np.zeros(capacity, dtype=np.int8)
        self.units = np.zeros(capacity, dtype=np.int32)
        self.host_units = np.zeros(capacity, dtype=np.int32)
        self.behind_sweep = np.zeros(capacity, dtype=bool)
        self.n_kept = 0
        self.n_seen = 0

    def observe(self, kind: str, lat: float, units: int, host_units: int,
                behind_sweep: bool) -> None:
        self.n_seen += 1
        if self.n_kept < self.capacity:
            slot = self.n_kept
            self.n_kept += 1
        else:
            slot = int(self._rng.integers(0, self.n_seen))
            if slot >= self.capacity:
                return
        self.lat[slot] = lat
        self.kind[slot] = KIND_CODES.get(kind, 0)
        self.units[slot] = units
        self.host_units[slot] = host_units
        self.behind_sweep[slot] = behind_sweep

    def table(self, q: float = 0.99) -> dict:
        """Why-slow composition of the tail above the q-quantile,
        grouped by (component kind, served-from)."""
        n = self.n_kept
        if n == 0:
            return {"q": q, "threshold_us": 0.0, "n_sampled": 0,
                    "n_tail": 0, "rows": []}
        lat = self.lat[:n]
        thresh = float(np.quantile(lat, q))
        tail = lat >= thresh
        host = self.host_units[:n] > 0
        rows = []
        for code, kname in enumerate(KIND_NAMES):
            for served, smask in (("hbm", ~host), ("host", host)):
                mask = tail & (self.kind[:n] == code) & smask
                cnt = int(mask.sum())
                if cnt == 0:
                    continue
                rows.append({
                    "kind": kname,
                    "served": served,
                    "count": cnt,
                    "share": cnt / max(1, int(tail.sum())),
                    "mean_lat_us": float(lat[mask].mean()) * 1e6,
                    "mean_units": float(self.units[:n][mask].mean()),
                    "mean_host_units":
                        float(self.host_units[:n][mask].mean()),
                    "behind_sweep":
                        int(self.behind_sweep[:n][mask].sum()),
                })
        rows.sort(key=lambda r: -r["count"])
        return {"q": q, "threshold_us": thresh * 1e6, "n_sampled": n,
                "n_seen": self.n_seen, "n_tail": int(tail.sum()),
                "rows": rows}

    def format_table(self, q: float = 0.99, title: str = "") -> str:
        t = self.table(q)
        head = (f"p{int(q * 1000) / 10:g} token attribution"
                f"{' — ' + title if title else ''}: "
                f"threshold {t['threshold_us']:.2f}us, "
                f"{t['n_tail']}/{t['n_sampled']} sampled accesses in tail")
        if not t["rows"]:
            return head + "\n  (no sampled accesses)"
        cols = (f"  {'kind':<7} {'served':<6} {'count':>6} {'share':>6} "
                f"{'mean_us':>9} {'units':>6} {'host':>5} {'sweep':>6}")
        lines = [head, cols]
        for r in t["rows"]:
            lines.append(
                f"  {r['kind']:<7} {r['served']:<6} {r['count']:>6} "
                f"{r['share']:>6.2f} {r['mean_lat_us']:>9.2f} "
                f"{r['mean_units']:>6.1f} {r['mean_host_units']:>5.1f} "
                f"{r['behind_sweep']:>6}")
        return "\n".join(lines)

    def summary(self, q: float = 0.99) -> dict:
        return self.table(q)


class ServingObservability:
    """Tracer + token attribution behind one flag.

    ``attach(component, name=...)`` wires a tiering component (and its
    RALT tracker, on the same track) or the `ServeEngine`."""

    def __init__(self, enabled: bool = True, trace: bool = True,
                 attribution: bool = True, attr_capacity: int = 65536,
                 max_events: int = 400_000):
        self.enabled = enabled
        self.tracer = _ProfilerClockTracer(clock=time.time_ns,
                                           max_events=max_events,
                                           enabled=enabled and trace)
        self.attr = TokenAttributionSampler(capacity=attr_capacity)
        self.attribution = enabled and attribution

    # -- gate (once per top-level call) --------------------------------
    def poll(self) -> bool:
        """Whether this top-level call records.  An attached plane
        records always, and annotates the profiler's trace while a
        session records."""
        if self.enabled:
            self.tracer.annotation = _Annotation if _is_profiling() else None
        return self.enabled

    # -- attachment ----------------------------------------------------
    def attach(self, comp, name: str = "serving") -> "ServingObservability":
        comp._obs = self
        comp._obs_track = name
        tracker = getattr(comp, "tracker", None)
        if tracker is not None:
            tracker._obs = self
            tracker._obs_track = name
        return self

    # -- counter lanes (end of each sweep / flush) ---------------------
    def sample(self, comp, track: str) -> None:
        """The component's `pool` lane (occupancy, staging depth, hit
        rate) and, where it counts them, its `bytes` lane."""
        s = component_sample(comp)
        self.tracer.counter(track, "pool", {k: round(s[k], 6)
                                            for k in POOL_LANE if k in s})
        moved = {k: s[k] for k in BYTES_LANE if k in s}
        if moved:
            self.tracer.counter(track, "bytes", moved)

    # -- export --------------------------------------------------------
    def export(self, trace_path: str | None = None,
               metrics_path: str | None = None) -> None:
        """The trace as Chrome/Perfetto JSON; `metrics_path` gets the
        counter samples alone."""
        if trace_path:
            self.tracer.export(trace_path)
        if metrics_path:
            with open(metrics_path, "w") as f:
                json.dump([ev for ev in self.tracer.events
                           if ev["ph"] == "C"], f)


class _ProfiledServingObservability(ServingObservability):
    """The class-level default plane: records exactly while a
    `jax.profiler` session records, and starts an empty log with each
    new session."""

    def __init__(self):
        super().__init__(enabled=False, attribution=False)
        self.tracer.enabled = True      # the sites gate on self.enabled
        self._session = None
        self._gc_open = False

    def poll(self) -> bool:
        on = _is_profiling()
        if on:
            if not self.enabled or _profiler_session() is not self._session:
                self._start()
        elif self.enabled:
            self._stop()
        return on

    def _start(self) -> None:
        self.tracer.clear()
        self._session = _profiler_session()
        self.enabled = True
        self.tracer.annotation = _Annotation
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _stop(self) -> None:
        self.enabled = False
        self.tracer.annotation = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        """`host/gc`: a collection while the session records (the hook
        outlives `stop_trace` until the next poll)."""
        if phase == "start" and self.enabled and _is_profiling():
            self._gc_open = True
            self.tracer.begin("host", "host/gc",
                              {"generation": info["generation"]})
        elif phase == "stop" and self._gc_open:
            self._gc_open = False
            self.tracer.end("host", "host/gc",
                            {"collected": info["collected"]})


# The default of every tiering/serving class's `_obs`.
DEFAULT_SERVING_OBS = _ProfiledServingObservability()
