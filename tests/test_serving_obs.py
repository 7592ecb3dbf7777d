"""Serving-side observability plane (repro.obs.serving): with no
profiler session the default plane records nothing, components pickle
cleanly, a disabled plane stays under the 3% overhead budget; span
nesting validates across a forced eviction sweep and a bulk staging
flush; all three page-level pathway instants and the seeded
version-mismatch promotion abort appear; the counter lanes are sampled
at the end of each sweep and flush; the engine's step budget is no
longer silent.
"""
import gc
import pickle
import time

import numpy as np
import pytest

from repro.configs import smoke_config
from repro.obs.serving import (DEFAULT_SERVING_OBS, ServingObservability,
                               TokenAttributionSampler, component_sample)
from repro.serving.engine import Request, ServeEngine
from repro.tiering import (ExpertCache, KVTierConfig, TieredEmbedding,
                           TieredKVCache)


def small_cfg(**kw):
    base = dict(n_pages=64, fast_slots=8, page_tokens=2, kv_heads=1,
                head_dim=4, staging_slots=4, sweep_every=16)
    base.update(kw)
    return KVTierConfig(**base)


def drive(kv, n_ops=200, seed=0, zipf=1.5, width=4):
    rng = np.random.default_rng(seed)
    for _ in range(n_ops):
        kv.read_pages(rng.zipf(zipf, width) % kv.cfg.n_pages)


# ----------------------------------------------------------------------
# disabled mode: zero events, pickles cleanly, bounded overhead
# ----------------------------------------------------------------------
def test_disabled_serving_obs_records_nothing():
    """With no profiler session the default plane records nothing (its
    log may still hold an earlier session's events)."""
    kv = TieredKVCache(small_cfg())
    assert kv._obs is DEFAULT_SERVING_OBS
    before = list(DEFAULT_SERVING_OBS.tracer.events)
    drive(kv)
    assert DEFAULT_SERVING_OBS.enabled is False
    assert DEFAULT_SERVING_OBS.tracer.events == before
    assert DEFAULT_SERVING_OBS.attr.n_seen == 0


def test_components_pickle_cleanly():
    """Unattached AND attached components round-trip through pickle:
    __getstate__ drops the plane (and HotTracker's jitted closures),
    the class-level default plane reasserts on load."""
    kv = TieredKVCache(small_cfg())
    drive(kv, 64)
    emb = TieredEmbedding(np.zeros((32, 4), np.float32), fast_rows=8)
    ec = ExpertCache(np.zeros((8, 2, 2), np.float32), fast_experts=2)
    obs = ServingObservability()
    for comp, name in ((kv, "kv"), (emb, "emb"), (ec, "expert")):
        obs.attach(comp, name)
    for comp in (kv, emb, ec):
        clone = pickle.loads(pickle.dumps(comp))
        assert clone._obs is DEFAULT_SERVING_OBS
        assert clone.clock.total_s == comp.clock.total_s
    kv2 = pickle.loads(pickle.dumps(kv))
    drive(kv2, 16)                    # rebuilt tracker jits still work
    assert kv2.clock.fast_hits > kv.clock.fast_hits


def test_disabled_serving_overhead_under_3_percent():
    """Paired adjacent-in-time runs cancel machine-load drift, CPU time
    ignores scheduler noise, and the medians filter jax-dispatch
    outliers; the ratio of the pooled medians (attached-disabled over
    unattached) must stay inside the 3% budget.  Up to two retries
    (after an explicit gc) absorb the allocator/GC spikes a loaded
    suite or shared CI runner can land on a measurement."""
    def one_run(attach_disabled: bool) -> float:
        kv = TieredKVCache(small_cfg(n_pages=128, fast_slots=16))
        if attach_disabled:
            ServingObservability(enabled=False).attach(kv, "off")
        t0 = time.process_time()
        drive(kv, 400, seed=3)
        return time.process_time() - t0

    def measured_ratio() -> float:
        gc.collect()                         # shed prior tests' garbage
        one_run(False), one_run(True)        # warm caches/jits
        base, dis = [], []
        for i in range(8):
            if i % 2 == 0:                   # alternate order in the pair
                base.append(one_run(False))
                dis.append(one_run(True))
            else:
                dis.append(one_run(True))
                base.append(one_run(False))
        return float(np.median(dis)) / float(np.median(base))

    ratios = [measured_ratio()]
    while min(ratios) >= 1.03 and len(ratios) < 3:
        ratios.append(measured_ratio())
    assert min(ratios) < 1.03, ratios


# ----------------------------------------------------------------------
# spans + pathway instants
# ----------------------------------------------------------------------
def test_span_nesting_across_sweep_and_flush():
    """Force both maintenance shapes — the scheduled eviction sweep and
    the bulk staging flush — and require a schema-clean trace that
    contains both spans plus pathway instants."""
    kv = TieredKVCache(small_cfg())
    obs = ServingObservability().attach(kv, "kv")
    drive(kv, 64)                       # staging_slots=4: flushes fire
    kv.sweep()                          # forced eviction sweep
    assert kv.clock.sweeps >= 1 and kv.clock.flushes >= 1
    assert obs.tracer.validate() == []
    names = obs.tracer.names()
    assert "kv/sweep" in names and "kv/staging_flush" in names
    assert "page/retained" in names
    assert names & {"page/promo_flush", "page/promo_compaction"}
    # B/E pairing: every begin has a matching end per track
    by_ph = {}
    for ev in obs.tracer.events:
        by_ph.setdefault((ev["track"], ev["ph"]), 0)
        by_ph[(ev["track"], ev["ph"])] += 1
    assert by_ph.get(("kv", "B"), 0) == by_ph.get(("kv", "E"), 0) > 0


def test_all_three_pathways_emit_instants():
    """Zipf traffic over a small pool drives retention (sweep keeps hot
    residents), promotion-by-flush (staging fills between sweeps), and
    promotion-by-compaction (sweep drains staged pages)."""
    kv = TieredKVCache(small_cfg(staging_slots=4, sweep_every=8))
    obs = ServingObservability().attach(kv, "kv")
    drive(kv, 300, zipf=1.3, width=6)
    kv.staging.clear()
    # demote a hot resident, stage it, sweep: promotion by compaction.
    # (Demoting first keeps pool occupancy under the auto-tuned hot
    # limit so the sweep's promotion is not skipped for lack of
    # headroom.)
    hot = np.asarray(kv._hot_set()).nonzero()[0]
    p = next(int(q) for q in hot if kv.tier[q] == kv.TIER_FAST)
    kv._demote(p)
    kv.staging[p] = int(kv.version[p])
    kv.sweep()
    names = obs.tracer.names()
    assert {"page/retained", "page/promo_compaction",
            "page/promo_flush"} <= names, sorted(names)
    assert obs.tracer.validate() == []


def test_version_mismatch_abort_emits_instant():
    """§3.3/3.4 hazard: a page staged at version v, overwritten to
    v+1, must abort its promotion and emit page/promo_abort."""
    kv = TieredKVCache(small_cfg())
    obs = ServingObservability().attach(kv, "kv")
    page = 5
    for _ in range(8):
        kv.read_pages([page])           # hot + staged
    staged = int(kv.version[page])
    kv.staging[page] = staged
    z = np.zeros((1, 2, 1, 4), np.float32)
    kv.write_page(page, z, z)           # bump version: stage is stale
    assert kv._promote(page, staged, hot=True) is False
    assert kv.clock.aborted == 1
    aborts = [e for e in obs.tracer.events
              if e["name"] == "page/promo_abort"]
    assert len(aborts) == 1
    args = aborts[0]["args"]
    assert args["page"] == page
    assert args["version"] == args["staged_version"] + 1


# ----------------------------------------------------------------------
# metrics + attribution
# ----------------------------------------------------------------------
def test_counter_lanes_at_sweep_end():
    """Each sweep and flush ends with one `pool` and one `bytes` counter
    sample, read from the component's own aggregates."""
    kv = TieredKVCache(small_cfg())
    obs = ServingObservability().attach(kv, "kv")
    drive(kv, 150)
    evs = obs.tracer.events
    ends = [i for i, e in enumerate(evs) if e["ph"] == "E"
            and e["name"] in ("kv/sweep", "kv/staging_flush")]
    assert len(ends) == kv.clock.sweeps + kv.clock.flushes > 2
    for i in ends:
        assert [e["name"] for e in evs[i + 1:i + 3]] == ["pool", "bytes"]
        assert all(e["ph"] == "C" for e in evs[i + 1:i + 3])
    pool = [e["args"] for e in evs if e["ph"] == "C" and e["name"] == "pool"]
    assert all(set(a) == {"hbm_occupancy", "staging_depth", "page_hit_rate"}
               and 0.0 <= a["hbm_occupancy"] <= 1.0 for a in pool)
    moved = [e["args"] for e in evs if e["ph"] == "C" and e["name"] == "bytes"]
    assert moved[-1] == {"h2d_bytes": float(kv.h2d_bytes),
                         "d2h_bytes": float(kv.d2h_bytes)}
    h2d = [a["h2d_bytes"] for a in moved]
    assert h2d == sorted(h2d) and h2d[-1] > 0
    assert not {"pcie_bytes", "pcie_s", "hbm_s"} & obs.tracer.names()


def test_component_sample_reads_only():
    kv = TieredKVCache(small_cfg())
    drive(kv, 64)
    before = (kv.clock.total_s, kv.clock.promoted, len(kv.staging),
              list(kv.free_slots))
    s = component_sample(kv)
    assert (kv.clock.total_s, kv.clock.promoted, len(kv.staging),
            list(kv.free_slots)) == before
    assert 0.0 <= s["page_hit_rate"] <= 1.0
    assert 0.0 <= s["hbm_occupancy"] <= 1.0
    assert s["h2d_bytes"] == kv.h2d_bytes == kv.cfg.page_bytes * (
        kv.clock.slow_hits + kv.clock.promoted)


def test_attribution_reservoir_and_table():
    attr = TokenAttributionSampler(capacity=64, seed=1)
    for i in range(500):
        attr.observe("kv", lat=float(i + 1) * 1e-6, units=4,
                     host_units=i % 3, behind_sweep=(i % 10 == 0))
    assert attr.n_seen == 500
    assert attr.n_kept == 64            # bounded
    t = attr.table(0.9)
    assert t["n_sampled"] == 64
    assert t["rows"], "tail rows must not be empty"
    assert abs(sum(r["share"] for r in t["rows"]) - 1.0) < 1e-9
    txt = attr.format_table(0.9, "unit")
    assert "kv" in txt and "unit" in txt


# ----------------------------------------------------------------------
# engine: the step budget is no longer silent
# ----------------------------------------------------------------------
def engine_with_requests(n_req=4, max_new=6):
    cfg = smoke_config("internvl2-1b")
    eng = ServeEngine(cfg, batch=2, max_len=48)
    rng = np.random.default_rng(0)
    for rid in range(n_req):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(0, cfg.vocab, 8)),
                           max_new=max_new))
    return eng


@pytest.mark.slow
def test_engine_spans_and_drain_counters():
    eng = engine_with_requests()
    obs = ServingObservability().attach(eng, "engine")
    done = eng.run()
    assert len(done) == 4
    assert eng.requests_completed == 4
    assert eng.steps_used > 0
    assert eng.starved is False
    names = obs.tracer.names()
    assert {"engine/prefill", "engine/decode", "engine/assign",
            "engine"} <= names
    assert "engine/starved" not in names
    assert obs.tracer.validate() == []


@pytest.mark.slow
def test_engine_starved_instant_on_budget_expiry():
    eng = engine_with_requests()
    obs = ServingObservability().attach(eng, "engine")
    eng.run(max_steps=5)
    assert eng.starved is True
    assert eng.steps_used == 5
    starved = [e for e in obs.tracer.events
               if e["name"] == "engine/starved"]
    assert len(starved) == 1
    args = starved[0]["args"]
    assert args["steps_used"] == 5
    assert args["live_slots"] + args["queued"] > 0
    assert obs.tracer.validate() == []   # spans closed despite the cut
