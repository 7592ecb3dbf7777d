"""Generator of multi-session page traffic against a tiered KV cache.

Each read is one decode step of one session: the session's first pages
(attention sinks), its last pages (the local window), and `count` pages
chosen among the rest, as page-sparse attention would select them.

Parameters (all in the traffic file):
    sessions, pages_per_session   session i owns pages
                                  [i * pages_per_session, (i+1) * ...)
    sink_pages, window_pages      pages read on every step of a session
    session_choice                {"kind": "zipf", "theta"} over a
                                  popularity ranking, or {"kind":
                                  "uniform"}
    page_choice                   the same, over a fixed per-session
                                  ranking of the middle pages; "count"
                                  distinct pages per read
    shift                         {"sessions", "every_reads"}: the ranking
                                  rotates by that many sessions every so
                                  many reads (0 sessions: never)
    write_prob                    after a read, the session's last page is
                                  rewritten with this probability (a page
                                  fills every page_tokens tokens)
    stream_seed                   the seed every draw above is made from
    warmup_reads                  reads made in set-up (the kv driver)
    trace                         {"from_s", "seconds"}: the stretch of
                                  the window a traced run profiles

The reads are the same for every run seed: which pages the fast tier
holds, and so what each read costs, follows from the order of all the
reads before it, so that order stays fixed.  The run seed draws the
bytes the pages hold (the driver's).
"""
from __future__ import annotations

import numpy as np


def _weights(choice: dict, n: int) -> np.ndarray | None:
    if choice["kind"] == "uniform":
        return None
    if choice["kind"] == "zipf":
        w = 1.0 / np.arange(1, n + 1) ** choice["theta"]
        return w / w.sum()
    raise ValueError(f"unknown choice {choice['kind']!r}")


class Stream:
    def __init__(self, traffic: dict):
        t = self.t = traffic
        self.rng = np.random.default_rng(t["stream_seed"])
        S, P = t["sessions"], t["pages_per_session"]
        self.n_pages = S * P
        self.sink = list(range(t["sink_pages"]))
        self.window = list(range(P - t["window_pages"], P))
        self.middle = np.arange(t["sink_pages"], P - t["window_pages"])
        self.session_rank = self.rng.permutation(S)
        self.page_rank = [self.rng.permutation(self.middle)
                          for _ in range(S)]
        self.p_session = _weights(t["session_choice"], S)
        self.p_page = _weights(t["page_choice"], len(self.middle))
        self.i = 0

    def next_read(self):
        """(session, sorted page ids, rewrite the last page after it)."""
        t = self.t
        S, P = t["sessions"], t["pages_per_session"]
        shift = t["shift"]
        offset = (shift["sessions"] * (self.i // shift["every_reads"])
                  if shift["sessions"] else 0)
        r = int(self.rng.choice(S, p=self.p_session))
        sess = int(self.session_rank[(r + offset) % S])
        picks = self.rng.choice(len(self.middle), t["page_choice"]["count"],
                                replace=False, p=self.p_page)
        local = self.sink + self.window + [int(self.page_rank[sess][k])
                                           for k in picks]
        write = bool(self.rng.random() < t["write_prob"])
        self.i += 1
        return sess, sorted(sess * P + p for p in local), write

    def last_page(self, sess: int) -> int:
        P = self.t["pages_per_session"]
        return sess * P + P - 1
