"""Tiered-KV / expert / embedding serving benchmark (the paper's §4
adapted to the TPU tiers; simulated device times, v5e HBM vs PCIe).

Systems compared at the page level (mirrors the paper's baselines):
  all-fast      everything in HBM (RocksDB-FD analogue; upper bound)
  no-promotion  pages stay where written (RocksDB-tiered analogue)
  seq-swap      whole-sequence granularity swapping (Mutant analogue,
                limitation 2: cold pages piggybacked with hot)
  hotrap        RALT-tracked page-granular retention + promotion

Workloads: hotspot-5%/zipfian/uniform page skew + a hotspot-shift
phase (paper Fig. 15 analogue).  Reported: simulated time, hit rate,
promotion traffic.

``--trace[=path]`` / ``--metrics-out[=path]`` attach the serving-side
observability plane (repro.obs.serving) and export a Perfetto trace /
its counter samples (`pool` and `bytes` lanes, taken at the end of each
sweep and flush); a "why slow" token-attribution table is printed
either way when the plane is live.  ``--smoke`` (CI bench-smoke job)
runs the quick shapes, gates on a schema-clean trace containing all
three page-level pathway instants plus the promotion-abort instant,
asserts the serving engine drained its queue, and writes
``BENCH_tiered_serving.json``.
"""
from __future__ import annotations

import sys

import numpy as np

from repro.tiering import KVTierConfig, TieredKVCache
from repro.tiering.kvcache import HBM_BW, PCIE_BW

from .common import finish_obs, make_serving_obs, write_bench_json


def make_kv(n_pages, fast_slots, **kw):
    cfg = KVTierConfig(n_pages=n_pages, fast_slots=fast_slots,
                       page_tokens=16, kv_heads=4, head_dim=32,
                       staging_slots=16, sweep_every=64, **kw)
    kv = TieredKVCache(cfg)
    z = np.zeros((1, cfg.page_tokens, cfg.kv_heads, cfg.head_dim),
                 np.float32)
    for p in range(n_pages):
        kv.write_page(p, z, z)
    kv.clock.pcie_s = kv.clock.hbm_s = 0.0      # don't count the load
    return kv


def access_stream(kind, n_pages, n_ops, seed=0, shift_at=None):
    rng = np.random.default_rng(seed)
    hot_lo = 0
    for i in range(n_ops):
        if shift_at and i == shift_at:
            hot_lo = n_pages // 2               # hotspot shift
        if kind == "hotspot":
            n_hot = max(n_pages // 20, 1)
            p = hot_lo + int(rng.integers(0, n_hot)) \
                if rng.random() < 0.95 else int(rng.integers(0, n_pages))
        elif kind == "zipf":
            p = (hot_lo + min(int(rng.zipf(1.2)) - 1, n_pages - 1)) \
                % n_pages
        else:
            p = int(rng.integers(0, n_pages))
        yield p % n_pages


def run_system(system, kind, n_pages=256, fast=32, n_ops=4000,
               shift_at=None, obs=None, track=None):
    kv = make_kv(n_pages, fast)
    if obs is not None:
        obs.attach(kv, track or f"kv/{kind}/{system}")
    if system == "all-fast":
        # upper bound: charge HBM for everything
        page_b = kv.cfg.page_bytes
        n = 0
        for _ in access_stream(kind, n_pages, n_ops, shift_at=shift_at):
            n += 1
        return dict(sim_s=n * page_b / HBM_BW, hit_rate=1.0, promoted=0)
    if system == "no-promotion":
        kv._promote = lambda *a, **k: False
        kv.sweep = lambda: None
        kv._maybe_flush = lambda: None
    if system == "seq-swap":
        # sequence granularity: promotion moves 8-page blocks; the
        # block is chosen by the accessed page (cold neighbours ride
        # along and evict other residents) — limitation 2
        orig = kv._promote

        def block_promote(page, ver, hot):
            base = (page // 8) * 8
            ok = False
            for p in range(base, min(base + 8, kv.cfg.n_pages)):
                if kv.tier[p] == kv.TIER_SLOW:
                    ok |= bool(orig(p, int(kv.version[p]), hot))
            return ok
        kv._promote = block_promote
    for p in access_stream(kind, n_pages, n_ops, shift_at=shift_at):
        kv.read_pages([p])
    return dict(sim_s=kv.clock.total_s, hit_rate=kv.fast_hit_rate(),
                promoted=kv.clock.promoted)


def abort_exercise(obs) -> None:
    """Deterministically drive the §3.3/3.4 version hazard: stage a
    page, bump its version (a prefill overwrite racing the copy), then
    promote with the stale staged version — the promotion must abort
    and emit its `page/promo_abort` instant."""
    kv = make_kv(32, 8)
    obs.attach(kv, "kv/abort")
    page = 3
    for _ in range(8):                        # make the page hot
        kv.read_pages([page])
    staged = int(kv.version[page])
    kv.staging[page] = staged
    z = np.zeros((1, kv.cfg.page_tokens, kv.cfg.kv_heads,
                  kv.cfg.head_dim), np.float32)
    kv.write_page(page, z, z)                 # version bump: stale stage
    assert not kv._promote(page, staged, hot=True)
    assert kv.clock.aborted >= 1


def engine_exercise(obs) -> dict:
    """Small end-to-end ServeEngine wave; the bench asserts it drains
    (satellite: the step budget is no longer silent)."""
    from repro.configs import smoke_config
    from repro.serving.engine import Request, ServeEngine

    cfg = smoke_config("internvl2-1b")
    eng = ServeEngine(cfg, batch=2, max_len=32)
    obs.attach(eng, "engine")
    rng = np.random.default_rng(0)
    n_req = 3
    for rid in range(n_req):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(0, cfg.vocab, 6)),
                           max_new=4))
    eng.run()
    return dict(steps_used=eng.steps_used,
                requests_completed=eng.requests_completed,
                submitted=n_req, starved=eng.starved)


def run_all(quick: bool = False, obs=None) -> dict:
    n_ops = 1500 if quick else 4000
    results: dict = {}
    for kind in ("hotspot", "zipf", "uniform"):
        rows = {}
        for system in ("all-fast", "hotrap", "seq-swap", "no-promotion"):
            r = run_system(system, kind, n_ops=n_ops, obs=obs)
            rows[system] = r
            print(f"kv_{kind}_{system},{r['sim_s'] * 1e6 / n_ops:.3f},"
                  f"hit={r['hit_rate']:.3f} promoted={r['promoted']}",
                  flush=True)
        base = rows["no-promotion"]["sim_s"]
        rows["speedup"] = base / rows["hotrap"]["sim_s"]
        print(f"kv_{kind}_speedup,{rows['speedup']:.2f},"
              f"hotrap_over_no_promotion", flush=True)
        results[kind] = rows
    # hotspot shift (Fig. 15 analogue)
    r = run_system("hotrap", "hotspot", n_ops=n_ops,
                   shift_at=n_ops // 2, obs=obs, track="kv/shift")
    print(f"kv_shift_hotrap,{r['sim_s'] * 1e6 / n_ops:.3f},"
          f"hit={r['hit_rate']:.3f} (recovers after shift)", flush=True)
    results["shift"] = r

    # embedding rows (zipf vocab) + expert cache
    from repro.tiering import TieredEmbedding, ExpertCache
    rng = np.random.default_rng(0)
    V, d = 4096, 64
    table = rng.standard_normal((V, d)).astype(np.float32)
    emb = TieredEmbedding(table, fast_rows=512, staging_slots=64)
    if obs is not None:
        obs.attach(emb, "emb")
    for _ in range(200 if quick else 400):
        ids = np.minimum(rng.zipf(1.3, 64) - 1, V - 1)
        emb.lookup(ids)
    print(f"embedding_zipf,{emb.clock.total_s * 1e6:.1f},"
          f"hit={emb.fast_hit_rate():.3f} promoted={emb.clock.promoted}",
          flush=True)
    results["embedding"] = dict(sim_s=emb.clock.total_s,
                                hit_rate=emb.fast_hit_rate(),
                                promoted=emb.clock.promoted)

    E = 64
    ec = ExpertCache(rng.standard_normal((E, 32, 32)).astype(np.float32),
                     fast_experts=16, swap_every=8)
    if obs is not None:
        obs.attach(ec, "expert")
    counts = None
    for _ in range(150 if quick else 300):
        e_ids = np.minimum(rng.zipf(1.4, 128) - 1, E - 1)
        counts = np.bincount(e_ids, minlength=E)
        ec.route(counts)
    print(f"expert_zipf,{ec.clock.total_s * 1e6:.1f},"
          f"resident_frac={ec.resident_fraction(counts):.3f}",
          flush=True)
    results["expert"] = dict(
        sim_s=ec.clock.total_s,
        resident_fraction=ec.resident_fraction(counts))
    return results


# The page-level pathway instants every smoke trace must contain
# (ARCHITECTURE.md maps these to the core plane's promo/* spans).
PATHWAY_EVENTS = {"page/retained", "page/promo_compaction",
                  "page/promo_flush"}


def smoke() -> None:
    """CI tripwire (see .github/workflows/ci.yml bench-smoke)."""
    failures = []
    # The plane rides along even without --trace so the span gates
    # below always run; files are only written when asked for.
    obs, trace_path, metrics_path = make_serving_obs("tiered_serving",
                                                     force=True)
    abort_exercise(obs)
    results = run_all(quick=True, obs=obs)
    engine = engine_exercise(obs)
    results["engine"] = engine
    if engine["starved"] or (engine["requests_completed"]
                             != engine["submitted"]):
        failures.append(f"engine did not drain: {engine}")
    hit = results["hotspot"]["hotrap"]["hit_rate"]
    if hit < results["hotspot"]["no-promotion"]["hit_rate"]:
        failures.append(f"hotrap hotspot hit rate {hit:.3f} below "
                        f"no-promotion baseline")
    names = obs.tracer.names()
    missing = (PATHWAY_EVENTS | {"page/promo_abort", "kv/sweep",
                                 "kv/staging_flush", "engine/prefill",
                                 "engine/decode"}) - names
    if missing:
        failures.append(f"trace is missing event types: {sorted(missing)}")
    problems = obs.tracer.validate()
    if problems:
        failures.append(f"trace schema problems: {problems[:5]}")
    if obs.attr.n_seen == 0:
        failures.append("attribution sampler saw zero accesses")
    print(obs.attr.format_table(0.99, "tiered_serving"), flush=True)
    write_bench_json("tiered_serving", results)
    finish_obs(obs, trace_path, metrics_path)
    if failures:
        for f in failures:
            print(f"SMOKE FAIL: {f}", flush=True)
        raise SystemExit(1)
    print(f"SMOKE OK: hotspot speedup "
          f"{results['hotspot']['speedup']:.2f}x, hit={hit:.3f}, "
          f"engine drained in {engine['steps_used']} steps, "
          f"{len(obs.tracer.events)} trace events", flush=True)


def main(quick: bool = False):
    obs, trace_path, metrics_path = make_serving_obs("tiered_serving")
    run_all(quick=quick, obs=obs)
    if obs is not None:
        print(obs.attr.format_table(0.99, "tiered_serving"), flush=True)
    finish_obs(obs, trace_path, metrics_path)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main(quick="--quick" in sys.argv)
