"""The serving plane on the profiler's clock: a program span starts where
the same span starts in the `.xplane.pb`; the default plane records only
while a profiler session records and starts empty in each new one; spans
know their parents; the engine stamps its own request times; the tier
manager counts the bytes it moves."""
import gc
import glob

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.obs.serving import DEFAULT_SERVING_OBS, ServingObservability
from repro.serving.engine import Request, ServeEngine
from repro.tiering import KVTierConfig, TieredKVCache


def small_cfg(**kw):
    base = dict(n_pages=64, fast_slots=8, page_tokens=2, kv_heads=1,
                head_dim=4, staging_slots=4, sweep_every=16)
    base.update(kw)
    return KVTierConfig(**base)


def drive(kv, n_ops=100, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n_ops):
        kv.read_pages(rng.zipf(1.5, 4) % kv.cfg.n_pages)


def spans(events):
    """{id: (B event, E event)} of the closed spans."""
    begun, out = {}, {}
    for ev in events:
        if ev["ph"] == "B":
            begun[ev["id"]] = ev
        elif ev["ph"] == "E" and ev.get("id") in begun:
            out[ev["id"]] = (begun.pop(ev["id"]), ev)
    return out


def test_program_span_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData
    kv = TieredKVCache(small_cfg())
    drive(kv, 4)                                    # compile outside
    with jax.profiler.trace(str(tmp_path)):
        kv.read_pages([1, 2, 3])
    mine = next(ev for ev in DEFAULT_SERVING_OBS.tracer.events
                if ev["name"] == "kv/read_pages" and ev["ph"] == "B")
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = dict(next(p for p in pd.planes
                      if p.name == "Task Environment").stats)
    t0 = start["profile_start_time"]
    found = {e.name: t0 + e.start_ns for p in pd.planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    # every span is also a TraceAnnotation of the same name
    assert {"kv/read_pages", "kv/lookup", "kv/gather", "kv/host_fetch",
            "kv/track", "ralt/record"} <= set(found)
    assert abs(mine["ts"] * 1e3 - found["kv/read_pages"]) < 200e3


def test_default_plane_gate(tmp_path):
    """No session: nothing recorded.  A session: recorded, and a second
    session, begun right after the first, starts with an empty log."""
    kv = TieredKVCache(small_cfg())
    assert kv._obs is DEFAULT_SERVING_OBS
    drive(kv, 4)
    before = list(DEFAULT_SERVING_OBS.tracer.events)
    drive(kv, 4)
    assert DEFAULT_SERVING_OBS.tracer.events == before
    assert not DEFAULT_SERVING_OBS.enabled
    with jax.profiler.trace(str(tmp_path / "a")):
        kv.read_pages([1, 2])
        kv.read_pages([3, 4])
    first = [ev for ev in DEFAULT_SERVING_OBS.tracer.events
             if ev["name"] == "kv/read_pages" and ev["ph"] == "B"]
    assert [ev["args"]["read"] for ev in first] == [8, 9]
    with jax.profiler.trace(str(tmp_path / "b")):
        kv.read_pages([5, 6])
    reads = [ev["args"]["read"] for ev in DEFAULT_SERVING_OBS.tracer.events
             if ev["name"] == "kv/read_pages" and ev["ph"] == "B"]
    assert reads == [10]
    assert DEFAULT_SERVING_OBS.tracer.validate() == []
    drive(kv, 1)                        # the next call sees no session
    assert not DEFAULT_SERVING_OBS.enabled
    assert DEFAULT_SERVING_OBS._on_gc not in gc.callbacks


def test_gc_spans_only_while_a_session_records(tmp_path):
    kv = TieredKVCache(small_cfg())
    drive(kv, 2)
    with jax.profiler.trace(str(tmp_path)):
        kv.read_pages([1])
        gc.collect()
        kv.read_pages([2])
    got = [(b, e) for b, e in spans(DEFAULT_SERVING_OBS.tracer.events)
           .values() if b["name"] == "host/gc"]
    assert got and all(b["track"] == "host" and b["parent"] is None
                       for b, _ in got)
    assert got[-1][0]["args"]["generation"] == 2
    assert "collected" in got[-1][1]["args"]
    n = len(DEFAULT_SERVING_OBS.tracer.events)
    kv.read_pages([3])
    gc.collect()
    assert len(DEFAULT_SERVING_OBS.tracer.events) == n


def test_read_and_write_span_trees():
    """`kv/host_fetch` and the RALT tracker's spans nest under the read
    that runs them; children's times add up to no more than the parent's;
    each read and write ends with the bytes it moved."""
    kv = TieredKVCache(small_cfg())
    obs = ServingObservability().attach(kv, "kv")
    drive(kv, 64)
    z = np.zeros((1, 2, 1, 4), np.float32)
    kv.write_page(int(kv.page_of_slot[0]), z, z)        # resident
    kv.write_page(int(np.flatnonzero(kv.tier == kv.TIER_SLOW)[0]), z, z)
    assert obs.tracer.validate() == []
    sp = spans(obs.tracer.events)
    by_name = {}
    for b, e in sp.values():
        by_name.setdefault(b["name"], []).append((b, e))
    for b, _ in by_name["kv/host_fetch"] + by_name["kv/lookup"]:
        assert sp[b["parent"]][0]["name"] == "kv/read_pages"
    for b, _ in by_name["ralt/record"]:
        assert sp[b["parent"]][0]["name"] == "kv/track"
    for name in ("ralt/limits", "ralt/hot_mask"):
        for b, _ in by_name[name]:
            assert sp[b["parent"]][0]["name"] in ("kv/sweep",
                                                  "kv/staging_flush")
    for b, _ in by_name["kv/sweep"] + by_name["kv/staging_flush"]:
        assert sp[b["parent"]][0]["name"] == "kv/read_pages"
    kids = {}
    for b, e in sp.values():
        if b["parent"] is not None:
            kids[b["parent"]] = kids.get(b["parent"], 0.0) + e["ts"] - b["ts"]
    for sid, (b, e) in sp.items():
        assert kids.get(sid, 0.0) <= e["ts"] - b["ts"] + 1e-6
    reads = by_name["kv/read_pages"]
    assert len(reads) == 64
    assert sum(e["args"]["h2d_bytes"] for _, e in reads) == \
        kv.cfg.page_bytes * (kv.clock.slow_hits + kv.clock.promoted)
    writes = by_name["kv/write_page"]
    assert [b["args"]["tier"] for b, _ in writes] == [0, 1]
    assert [[c["name"] for c, _ in sp.values() if c["parent"] == b["id"]]
            for b, _ in writes] == [["kv/write_stage", "kv/write_resident"],
                                    ["kv/write_stage", "kv/write_host"]]
    pool = kv.cfg.fast_slots * kv.cfg.page_bytes
    assert [e["args"]["pool_copy_bytes"] for _, e in writes] == [pool, 0]
    assert [e["args"]["h2d_bytes"] for _, e in writes] == \
        [kv.cfg.page_bytes, 0]


def test_byte_counters():
    """h2d_bytes = page bytes x (slow page reads + promotions + resident
    rewrites); d2h_bytes = page bytes x demotions; each promotion and
    resident rewrite copies the whole pool."""
    kv = TieredKVCache(small_cfg(sweep_every=8))
    z = np.zeros((1, 2, 1, 4), np.float32)
    for p in range(kv.cfg.n_pages):
        kv.write_page(p, z, z)          # every page starts on the host
    assert kv.h2d_bytes == kv.pool_copy_bytes == 0
    drive(kv, 200, seed=4)
    rewrites = 0
    for p in np.flatnonzero(kv.tier == kv.TIER_FAST)[:3]:
        kv.write_page(int(p), z, z)
        rewrites += 1
    c, pb = kv.clock, kv.cfg.page_bytes
    assert c.promoted > 0 and c.slow_hits > 0
    assert kv.h2d_bytes == pb * (c.slow_hits + c.promoted + rewrites)
    assert kv.d2h_bytes == pb * c.demoted
    assert kv.pool_copy_bytes == \
        kv.cfg.fast_slots * pb * (c.promoted + rewrites)


@pytest.mark.slow
def test_engine_steps_and_request_times():
    """Each step's children are feed, dispatch, sync and emit, under the
    wave's prefill or decode span; every request has an admission and a
    first-token instant, in that order."""
    cfg = smoke_config("internvl2-1b")
    eng = ServeEngine(cfg, batch=2, max_len=48)
    rng = np.random.default_rng(0)
    for rid in range(3):
        eng.submit(Request(rid=rid, max_new=4,
                           prompt=list(rng.integers(0, cfg.vocab, 5))))
    obs = ServingObservability().attach(eng, "engine")
    eng.run()
    assert obs.tracer.validate() == []
    sp = spans(obs.tracer.events)
    steps = [b for b, _ in sp.values() if b["name"] == "engine/step"]
    assert len(steps) == eng.steps_used
    # two waves, each of its longest prompt and then its longest output
    assert [b["args"]["wave"] for b in steps] == [0] * 9 + [1] * 9
    for st in steps:
        assert sp[st["parent"]][0]["name"] in ("engine/prefill",
                                               "engine/decode")
        assert [b["name"] for b, _ in sp.values()
                if b["parent"] == st["id"]] == [
            "engine/feed", "engine/dispatch", "engine/sync", "engine/emit"]
    assert sum(b["name"] == "engine/reset" for b, _ in sp.values()) == 2
    at = {}
    for ev in obs.tracer.events:
        if ev["ph"] == "i" and ev["name"] in ("engine/admit",
                                              "engine/first_token"):
            at.setdefault(ev["args"]["rid"], []).append(
                (ev["name"], ev["ts"]))
    assert sorted(at) == [0, 1, 2]
    for rid, marks in at.items():
        assert [n for n, _ in marks] == ["engine/admit",
                                         "engine/first_token"]
        assert marks[0][1] <= marks[1][1]
