"""Tiering (TPU adaptation of the paper): tracker algebra, pathway
behaviour, concurrency hazards, and hit-rate claims at test scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.tiering.hotness import (HotTracker, TrackerConfig,
                                   current_scores, init_state,
                                   record_accesses, sampled_threshold)
from repro.tiering.kvcache import KVTierConfig, TieredKVCache
from repro.tiering.embedding import TieredEmbedding
from repro.tiering.expert_cache import ExpertCache


def small_cfg(n=64, **kw):
    d = dict(n_units=n, unit_bytes=1024, fast_bytes=16 * 1024,
             n_samples=64)
    d.update(kw)
    return TrackerConfig(**d)


# ----------------------------------------------------------------------
# hotness tracker
# ----------------------------------------------------------------------
def test_scores_decay_matches_paper_rule():
    """real_score(now) = alpha^(now - tick) * score (§3.2)."""
    cfg = small_cfg()
    st_ = init_state(cfg)
    hits = jnp.zeros(cfg.n_units, bool).at[3].set(True)
    st_ = record_accesses(st_, hits, cfg)
    s0 = float(current_scores(st_, cfg)[3])
    assert s0 == pytest.approx(1.0)
    tick3 = int(st_["tick"][3])
    # advance time slices by accessing other units a lot
    other = jnp.zeros(cfg.n_units, bool).at[jnp.arange(4, 20)].set(True)
    for _ in range(8):
        st_ = record_accesses(st_, other, cfg)
    dt = int(st_["now"]) - tick3
    assert dt > 0, "time slices should advance with accessed bytes"
    s1 = float(current_scores(st_, cfg)[3])
    assert s1 == pytest.approx(cfg.alpha ** dt, rel=1e-5)


@given(st.integers(1, 40), st.integers(0, 30))
@settings(max_examples=20, deadline=None)
def test_lazy_decay_composes(hits_a, gap):
    """Decaying (tick->t1) then (t1->t2) == decaying (tick->t2): the
    paper's merge rule is associative for any slice split."""
    alpha = 0.9
    s0, t0 = 3.0, 5
    t1, t2 = t0 + hits_a, t0 + hits_a + gap
    one = s0 * alpha ** (t2 - t0)
    two = (s0 * alpha ** (t1 - t0)) * alpha ** (t2 - t1)
    assert one == pytest.approx(two, rel=1e-9)


def test_hot_keys_become_stable_alg1():
    """Alg. 1: frequently-hit keys gain counters/tags; cold stay off."""
    cfg = small_cfg(n=128)
    tr = HotTracker(cfg)
    rng = np.random.default_rng(0)
    hot_ids = np.arange(8)
    for _ in range(60):
        ids = np.concatenate([hot_ids, rng.integers(8, 128, 4)])
        tr.record_ids(jnp.asarray(ids, jnp.int32))
    state = tr.state
    stable = np.asarray((state["c"] > 0) & state["t"])
    assert stable[:8].all(), "hot keys must become stable"
    assert stable[8:].mean() < 0.5, "most cold keys must stay unstable"
    tr.refresh_limits()
    hot = np.asarray(tr.hot())
    assert hot[:8].all()


def test_sampled_threshold_targets_fraction():
    """§3.2 sampling: threshold keeps ~target_bytes of the hottest."""
    cfg = small_cfg(n=1024, n_samples=256)
    state = init_state(cfg)
    # construct a known score distribution: unit i has score i
    state = {**state, "score": jnp.arange(1024, dtype=jnp.float32),
             "tick": jnp.zeros(1024, jnp.int32)}
    target = 0.25 * 1024 * cfg.unit_bytes       # keep hottest quarter
    thr = float(sampled_threshold(state, cfg, jnp.asarray(target)))
    kept = (np.arange(1024) >= thr).mean()
    assert 0.15 < kept < 0.35, (thr, kept)


def test_sampled_threshold_tables_over_2gib():
    """Real KV pages (5 MiB at stablelm-3b's widths): the tracked bytes
    exceed int32 and must not overflow the threshold arithmetic."""
    cfg = small_cfg(n=1024, n_samples=256, unit_bytes=5 * 2**20,
                    fast_bytes=256 * 5 * 2**20)
    state = {**init_state(cfg), "score": jnp.arange(1024, dtype=jnp.float32)}
    target = 0.25 * 1024 * cfg.unit_bytes
    thr = float(sampled_threshold(state, cfg, jnp.asarray(target)))
    assert 0.15 < (np.arange(1024) >= thr).mean() < 0.35, thr
    tr = HotTracker(cfg)
    tr.record_ids(jnp.arange(8))
    tr.refresh_limits()
    assert np.isfinite(float(tr.state["threshold"]))


# ----------------------------------------------------------------------
# tiered KV cache: pathways + concurrency hazard
# ----------------------------------------------------------------------
def kv_cfg(**kw):
    d = dict(n_pages=64, fast_slots=16, page_tokens=4, kv_heads=2,
             head_dim=8, staging_slots=8, sweep_every=32)
    d.update(kw)
    return KVTierConfig(**d)


def test_hot_pages_get_promoted():
    cfg = kv_cfg()
    kv = TieredKVCache(cfg)
    rng = np.random.default_rng(1)
    shape = (cfg.n_layers, cfg.page_tokens, cfg.kv_heads, cfg.head_dim)
    for p in range(cfg.n_pages):
        kv.write_page(p, rng.random(shape), rng.random(shape))
    hot_pages = list(range(8))
    for i in range(300):
        p = hot_pages[i % 8] if i % 10 < 9 else int(rng.integers(8, 64))
        kv.read_pages([p])
    assert kv.clock.promoted >= 8
    resident = {int(p) for p in kv.page_of_slot if p >= 0}
    assert set(hot_pages) <= resident, (hot_pages, resident)
    # late-phase reads should be mostly fast hits
    c0 = kv.clock.fast_hits
    for i in range(50):
        kv.read_pages([hot_pages[i % 8]])
    assert kv.clock.fast_hits - c0 == 50


def test_promotion_aborts_on_newer_version():
    """§3.3/3.4: a page updated after staging must NOT be promoted."""
    cfg = kv_cfg(staging_slots=4, sweep_every=10_000)
    kv = TieredKVCache(cfg)
    rng = np.random.default_rng(2)
    shape = (cfg.n_layers, cfg.page_tokens, cfg.kv_heads, cfg.head_dim)
    for p in range(cfg.n_pages):
        kv.write_page(p, rng.random(shape), rng.random(shape))
    # stage page 0 by reading it (it is slow-tier), then update it
    kv.read_pages([0])
    assert 0 in kv.staging
    newer = rng.random(shape)
    kv.write_page(0, newer, newer)
    # force a flush: fill staging with other hot-ish pages
    for i in range(200):
        kv.read_pages([i % 4])
    assert kv.clock.aborted >= 1
    # page 0 must serve the *newer* data wherever it lives
    got = np.asarray(kv.read_pages([0])[0])
    np.testing.assert_allclose(got[0], np.stack([newer, newer])[0],
                               rtol=1e-2, atol=1e-2)


def test_kv_reads_are_exact():
    cfg = kv_cfg()
    kv = TieredKVCache(cfg)
    rng = np.random.default_rng(3)
    shape = (cfg.n_layers, cfg.page_tokens, cfg.kv_heads, cfg.head_dim)
    ref = {}
    for p in range(cfg.n_pages):
        k, v = rng.random(shape), rng.random(shape)
        kv.write_page(p, k, v)
        ref[p] = np.stack([k, v])
    order = rng.permutation(np.repeat(np.arange(cfg.n_pages), 4))
    for p in order:
        got = np.asarray(kv.read_pages([int(p)])[0], np.float32)
        np.testing.assert_allclose(got, ref[int(p)], rtol=1e-2,
                                   atol=1e-2)


# ----------------------------------------------------------------------
# tiered embedding + expert cache
# ----------------------------------------------------------------------
def test_embedding_exact_and_hit_rate_improves():
    V, d = 512, 16
    rng = np.random.default_rng(4)
    table = rng.standard_normal((V, d)).astype(np.float32)
    emb = TieredEmbedding(table, fast_rows=64, staging_slots=16)
    # zipf-ish skew over 32 hot rows
    for step in range(80):
        ids = np.where(rng.random(32) < 0.9,
                       rng.integers(0, 32, 32),
                       rng.integers(0, V, 32))
        out = np.asarray(emb.lookup(ids))
        np.testing.assert_allclose(out, table[ids], rtol=1e-6)
    assert emb.clock.promoted > 0
    late = emb.clock.fast_hits
    total = emb.clock.fast_hits + emb.clock.slow_hits
    assert late / total > 0.5, emb.fast_hit_rate()


def test_expert_cache_tracks_skewed_routing():
    E = 32
    rng = np.random.default_rng(5)
    weights = rng.standard_normal((E, 8, 8)).astype(np.float32)
    ec = ExpertCache(weights, fast_experts=8, swap_every=8)
    hot = np.zeros(E, np.int64)
    for step in range(200):
        counts = np.zeros(E, np.int64)
        for _ in range(16):
            e = rng.integers(0, 4) if rng.random() < 0.9 \
                else rng.integers(0, E)
            counts[e] += 1
        ec.route(counts)
        hot = counts
    assert ec.resident_fraction(hot) > 0.8
    assert ec.clock.promoted >= 4
