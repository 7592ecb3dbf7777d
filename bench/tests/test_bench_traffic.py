"""The traffic generators: deterministic for a seed, the same work for
every seed, and the distributions their files state."""
import json
import statistics

import numpy as np

from bench.tests.conftest import ROOT, TINY
from bench.traffic import requests, sessions

BIG_SEED = 2**33 + 12345            # more than 32 signed bits hold


def traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def test_requests_same_seed_same_inputs():
    t = traffic("batch")
    a = requests.Stream(t, BIG_SEED, 50304)
    b = requests.Stream(t, BIG_SEED, 50304)
    assert [a.next_request() for _ in range(40)] == \
        [b.next_request() for _ in range(40)]


def test_requests_every_seed_same_sizes_other_order():
    t = traffic("batch")
    pool = t["size_pool"]
    blocks = []
    for seed in (1, 2, BIG_SEED):
        s = requests.Stream(t, seed, 50304)
        sizes = [s.next_sizes() for _ in range(3 * pool)]
        blocks.append([sorted(sizes[i:i + pool])
                       for i in range(0, 3 * pool, pool)])
    assert blocks[0] == blocks[1] == blocks[2]
    a = requests.Stream(t, 1, 50304)
    b = requests.Stream(t, 2, 50304)
    assert [a.next_sizes() for _ in range(pool)] != \
        [b.next_sizes() for _ in range(pool)]


def test_requests_clipped_to_the_stated_range():
    for name in ("batch", "chat"):
        t = traffic(name)
        s = requests.Stream(t, 3, 1000)
        for _ in range(200):
            r = s.next_request()
            assert t["prompt"]["min"] <= len(r["prompt"]) <= \
                t["prompt"]["max"]
            assert t["output"]["min"] <= r["max_new"] <= t["output"]["max"]
            assert all(0 <= i < 1000 for i in r["prompt"])


def test_gamma_arrivals_rate_and_burstiness():
    t = traffic("chat")
    t["arrival"] = dict(t["arrival"], rate_per_s=50.0)
    s = requests.Stream(t, 1, 100)
    due = s.arrivals(400.0)
    gaps = np.diff(due)
    assert abs(len(due) / 400.0 - 50.0) / 50.0 < 0.1
    cv = statistics.pstdev(gaps) / statistics.mean(gaps)
    assert 1.7 < cv < 2.3
    # the same arrival times for every run seed
    assert np.array_equal(due, requests.Stream(t, BIG_SEED, 100)
                          .arrivals(400.0))


def test_sessions_deterministic_and_well_formed():
    t = traffic("kv_skew")
    a = sessions.Stream(t)
    b = sessions.Stream(t)
    P = t["pages_per_session"]
    for _ in range(300):
        ra, rb = a.next_read(), b.next_read()
        assert ra == rb
        sess, pages, _ = ra
        assert len(set(pages)) == 1 + 2 + 8
        assert all(sess * P <= p < (sess + 1) * P for p in pages)
        assert sess * P in pages and sess * P + P - 1 in pages


def test_sessions_zipf_skew_and_rotation():
    t = traffic("kv_skew")
    every = t["shift"]["every_reads"]
    s = sessions.Stream(t)
    first = [s.next_read()[0] for _ in range(every)]
    second = [s.next_read()[0] for _ in range(every)]
    top1 = max(set(first), key=first.count)
    top2 = max(set(second), key=second.count)
    # Zipf 0.99 over 32: the top session takes about a quarter of reads
    assert first.count(top1) / every > 0.12
    assert top1 != top2
    # the ranking moved by 8 places: the old top is no longer favoured
    assert second.count(top1) / every < 0.08


def test_sessions_uniform_has_no_favourite():
    t = json.loads((TINY / "bench" / "traffic" / "tiny_kv_uniform.json")
                   .read_text())
    s = sessions.Stream(t)
    picks = [s.next_read()[0] for _ in range(3200)]
    counts = np.bincount(picks, minlength=t["sessions"])
    assert counts.max() / counts.mean() < 1.5


def test_sessions_write_share():
    t = traffic("kv_skew")
    s = sessions.Stream(t)
    writes = sum(s.next_read()[2] for _ in range(4000))
    assert abs(writes / 4000 - t["write_prob"]) < 0.02
