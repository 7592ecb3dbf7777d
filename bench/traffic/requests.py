"""Generator of serving requests from a traffic file.

Parameters (all in the traffic file):
    prompt, output      {"median", "sigma", "min", "max"}: lognormal token
                        counts, clipped
    size_pool           how many (prompt, output) pairs are drawn
    size_seed           the seed of those draws and of the arrival gaps
    order               "seed": each consecutive block of `size_pool`
                        requests holds the whole pool in an order drawn
                        from the run seed; "fixed": in the pool's order
    arrival             {"kind": "closed"}: a closed queue that never runs
                        dry; or {"kind": "gamma", "rate_per_s", "cv"}:
                        open-loop arrivals with gamma-distributed gaps of
                        that mean rate and coefficient of variation, and
                        "drain_s", how long after the close the driver
                        serves the requests already due
    trace               {"from_s", "seconds"}: the stretch of the window a
                        traced run profiles (the serving driver)

The work is the same for every run seed: the sizes and the arrival times
come from `size_seed`.  The run seed decides the prompts' token ids and,
with order "seed", which request takes which size.  A lockstep engine's
waves last as long as their longest request, so where the order decides
how requests share waves (an open loop) it stays fixed, and the seed
changes only the token ids.
"""
from __future__ import annotations

import numpy as np


def _lognormal(rng, p: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(p["median"]) + p["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


class Stream:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.vocab = vocab
        fixed = np.random.default_rng(traffic["size_seed"])
        n = traffic["size_pool"]
        self.pool = list(zip(_lognormal(fixed, traffic["prompt"], n),
                             _lognormal(fixed, traffic["output"], n)))
        self.rng = np.random.default_rng(seed)
        self._order: list = []

    def next_sizes(self):
        if not self._order:
            n = len(self.pool)
            self._order = (list(self.rng.permutation(n))
                           if self.t["order"] == "seed"
                           else list(range(n - 1, -1, -1)))
        prompt, out = self.pool[self._order.pop()]
        return int(prompt), int(out)

    def next_request(self) -> dict:
        """{"prompt": [ids], "max_new": n} of the next request."""
        prompt, out = self.next_sizes()
        ids = self.rng.integers(0, self.vocab, prompt)
        return {"prompt": [int(i) for i in ids], "max_new": out}

    def arrivals(self, seconds: float) -> np.ndarray:
        """Due times in [0, seconds) of an open loop (same for every
        run seed)."""
        a = self.t["arrival"]
        if a["kind"] != "gamma":
            raise ValueError(f"no arrival times for {a['kind']!r} traffic")
        shape = 1.0 / a["cv"] ** 2
        mean = 1.0 / a["rate_per_s"]
        n = int(seconds * a["rate_per_s"] * 3) + 16
        rng = np.random.default_rng([self.t["size_seed"], 1])
        due = np.cumsum(rng.gamma(shape, mean / shape, n))
        return due[due < seconds]

    def schedule(self, seconds: float) -> list:
        """[(due_s, request)] for every request due in the window."""
        return [(float(d), self.next_request())
                for d in self.arrivals(seconds)]
