"""Shared benchmark scaffolding.

Profiles scale the paper's 10 GB FD : 100 GB SD testbed down to
laptop-size while keeping every *ratio* (FD:SD = 1:10, DB ~110% of the
hierarchy, block 16 KiB, bloom 10 bits/key, hot set 5%).  Loaded DBs are
pickled once per (system, record size) and cloned per cell, and storage
accounting is reset after load so throughput reflects the run phase only
(the paper reports the final 10% of the run phase).
"""
from __future__ import annotations

import io
import json
import os
import pickle
import sys
import time

from repro.core import LSMConfig, ShardConfig
from repro.core.baselines import make_system
from repro.core.runner import BENCH_SCHEMA, db_key_count, load_db, run_workload
from repro.core.storage import MIB
from repro.obs import Observability, jsonify

PROFILES = {
    "quick":   dict(fd=4 * MIB, sd=40 * MIB, sstable=256 * 1024, n_ops=25_000),
    "default": dict(fd=8 * MIB, sd=80 * MIB, sstable=256 * 1024, n_ops=50_000),
    "full":    dict(fd=32 * MIB, sd=320 * MIB, sstable=512 * 1024,
                    n_ops=200_000),
}


def profile_name() -> str:
    for flag in ("--quick", "--full"):
        if flag in sys.argv:
            return flag[2:]
    return os.environ.get("REPRO_BENCH_PROFILE", "default")


def sanitize_enabled() -> bool:
    """`--sanitize` (or REPRO_SANITIZE=1) runs every engine these
    benchmarks build under the runtime sanitizer (core/sanitize.py):
    op-by-op invariant checks, and a `close()` sweep at teardown that
    raises on any Version-ref leak or stats-conservation break."""
    return "--sanitize" in sys.argv or os.environ.get("REPRO_SANITIZE") == "1"


def make_cfg(profile: str | None = None, **kw) -> LSMConfig:
    p = PROFILES[profile or profile_name()]
    cfg = LSMConfig(fd_size=p["fd"], sd_size=p["sd"],
                    target_sstable_bytes=p["sstable"],
                    memtable_bytes=p["sstable"],
                    block_cache_bytes=max(p["fd"] // 64, 64 * 1024))
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def n_ops(profile: str | None = None) -> int:
    return PROFILES[profile or profile_name()]["n_ops"]


# The three cluster policies the skew studies compare (used by
# benchmarks/shifting_hotspot.py and benchmarks/tail_latency.py — one
# definition so the two stay comparable).
SHARD_POLICIES = {
    "static":      dict(hot_budget=False, repartition=False),
    "arbiter":     dict(hot_budget=True, repartition=False),
    "repartition": dict(hot_budget=True, repartition=True),
}


def skew_shard_config(nk: int, phase_ops: int, n_shards: int = 4,
                      **knobs) -> ShardConfig:
    """Range-partitioned cluster recipe for the contiguous-skew
    studies: trigger cadences scale with the measurement phase length,
    the migration stream drains one shard (~nk/N records) in about a
    quarter phase, and the demand signal is the load-following
    ``fg_util`` (RALT hot-set estimates are per-run snapshots that
    decay only on access, so a shard that was hot a phase ago still
    advertises a big hot set and masks the newly hot shard)."""
    return ShardConfig(
        n_shards=n_shards, partitioning="range", key_space=nk,
        demand_signal="fg_util",
        rebalance_interval_ops=max(phase_ops // 12, 250),
        repartition_interval_ops=max(phase_ops // 8, 250),
        repartition_cooldown_ops=max(phase_ops // 16, 100),
        migration_records_per_op=max(
            4 * nk // max(n_shards * phase_ops, 1), 64),
        min_shards=2, max_shards=2 * n_shards,
        **knobs)


class LoadedDBCache:
    """Load once per (system, value_len), clone per benchmark cell."""

    def __init__(self):
        self._blobs: dict[tuple, bytes] = {}

    def get(self, system: str, cfg: LSMConfig, value_len: int, seed: int = 0):
        if sanitize_enabled():
            # the sanitizer wrapper holds live engine hooks and is not
            # picklable: load fresh (slower, but every op is checked —
            # including the load phase)
            db = make_system(system, cfg, seed=seed, sanitize=True)
            nk = db_key_count(cfg, value_len)
            load_db(db, nk, value_len, seed)
            db.reset_storage()
            return db, nk
        key = (system, cfg.fd_size, cfg.sd_size, value_len, seed)
        if key not in self._blobs:
            db = make_system(system, cfg, seed=seed)
            nk = db_key_count(cfg, value_len)
            load_db(db, nk, value_len, seed)
            buf = io.BytesIO()
            pickle.dump((db, nk), buf, protocol=pickle.HIGHEST_PROTOCOL)
            self._blobs[key] = buf.getvalue()
        db, nk = pickle.loads(self._blobs[key])
        db.reset_storage()
        return db, nk


DB_CACHE = LoadedDBCache()


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The harness CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


# -- observability plane (src/repro/obs) -----------------------------------

def flag_value(flag: str, default: str) -> str | None:
    """`--flag=path` -> path; bare `--flag` -> default; absent -> None."""
    for a in sys.argv:
        if a == flag:
            return default
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def make_obs(bench: str, force: bool = False):
    """(Observability | None, trace_path | None, metrics_path | None)
    for a benchmark process.  `--trace[=path]` records a Perfetto trace
    (default trace_<bench>.json); `--metrics-out[=path]` additionally
    dumps the cadenced metrics registry.  `force=True` builds the plane
    even without flags (smoke gates assert on span presence) — export
    still only happens for paths the user asked for."""
    tp = flag_value("--trace", f"trace_{bench}.json")
    mp = flag_value("--metrics-out", f"metrics_{bench}.json")
    if tp is None and mp is None and not force:
        return None, None, None
    return Observability(), tp, mp


def make_serving_obs(bench: str, force: bool = False):
    """Serving-half twin of `make_obs`: builds a
    `repro.obs.serving.ServingObservability` (trace, with its `pool` and
    `bytes` counter lanes, + token attribution) under the same
    `--trace`/`--metrics-out` flag contract; `--metrics-out` writes the
    counter samples.  `finish_obs` works for both planes."""
    from repro.obs.serving import ServingObservability
    tp = flag_value("--trace", f"trace_{bench}.json")
    mp = flag_value("--metrics-out", f"metrics_{bench}.json")
    if tp is None and mp is None and not force:
        return None, None, None
    return ServingObservability(), tp, mp


def finish_obs(obs, trace_path: str | None,
               metrics_path: str | None) -> None:
    """Export whatever the user asked for; prints the artifact paths."""
    if obs is None:
        return
    obs.export(trace_path=trace_path, metrics_path=metrics_path)
    for p in (trace_path, metrics_path):
        if p:
            print(f"# wrote {p}", flush=True)


def write_bench_json(bench: str, results: dict) -> str:
    """Every benchmark's --smoke writes BENCH_<bench>.json so CI can
    archive machine-readable telemetry next to the CSV lines.  Values
    that are RunResults go through their schema-versioned to_json();
    anything else is jsonified as-is."""
    payload = {k: (v.to_json() if hasattr(v, "to_json") else jsonify(v))
               for k, v in results.items()}
    path = os.path.join(os.environ.get("REPRO_BENCH_DIR", "."),
                        f"BENCH_{bench}.json")
    with open(path, "w") as f:
        json.dump({"schema": BENCH_SCHEMA, "bench": bench,
                   "profile": profile_name(), "results": payload}, f,
                  indent=1)
    print(f"# wrote {path}", flush=True)
    return path


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.wall = time.time() - self.t0
