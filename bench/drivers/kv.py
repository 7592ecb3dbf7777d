"""Tiered-KV driver: `TieredKVCache.read_pages` and `write_page` under
multi-session decode traffic, one stream in a closed loop.

Every page the benchmark writes holds known bytes: one of a few random
base blocks drawn from the seed, stamped with the page id and how often
it was written.  Each read is timed from the call until the returned
pages are ready on the device.  A write is timed until the pool it
changed is ready, so that its device work (a rewrite of a resident page
copies the whole pool) is its own and not the next read's.  Then,
outside the timed part of a read, a
position-weighted checksum of every returned page is taken on the device
and compared, after the window, with the checksum of what was last
written to that page.  For a sample of reads drawn from the seed the
tracker's state before and after the read is kept, and the RALT update
it made is compared with the plain reference.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.cell import load_module
from bench.reference import ralt as ralt_ref

READ_SPAN = "bench/read_pages"
WRITE_SPAN = "bench/write_page"
WINDOW_SPAN = "bench/window"
N_BASE = 8                  # random base blocks
TRACKER_SAMPLE = 0.25       # share of reads whose tracker update is kept
CONTROL_READS = 16          # reads replayed through the control's store


def _weights(n: int):
    i = np.arange(n, dtype=np.uint64)
    a = (i % 65521 + 1).astype(np.uint32)
    b = (((i * 2654435761) % 2**32) >> 8 | 1).astype(np.uint32)
    return a, b


def host_checksum(block) -> tuple:
    """Two position-weighted sums of the page's 16-bit words, mod 2^32."""
    u = np.ascontiguousarray(block).view(np.uint16).reshape(-1)
    a, b = _weights(u.size)
    u = u.astype(np.uint32)
    return (int(np.sum(u * a, dtype=np.uint32)),
            int(np.sum(u * b, dtype=np.uint32)))


@jax.jit
def device_checksums(pages):
    """The same two sums for each page of a tuple, on the device."""
    x = jnp.stack(pages)
    u = jax.lax.bitcast_convert_type(x, jnp.uint16).reshape(len(pages), -1)
    u = u.astype(jnp.uint32)
    i = jnp.arange(u.shape[1], dtype=jnp.uint32)
    a = i % 65521 + 1
    b = (i * jnp.uint32(2654435761)) >> 8 | 1
    return jnp.stack([jnp.sum(u * a, axis=1, dtype=jnp.uint32),
                      jnp.sum(u * b, axis=1, dtype=jnp.uint32)], axis=1)


class Contents:
    """The bytes of page p after its w-th write, and their checksum."""

    STAMP = 6               # leading elements that carry (page, w)

    def __init__(self, shape, dtype, seed: int):
        rng = np.random.default_rng([seed, 3])
        self.shape, self.dtype = shape, np.dtype(dtype)
        self.base = [rng.standard_normal(shape, np.float32)
                     .astype(self.dtype) for _ in range(N_BASE)]
        self.base_sum = [host_checksum(b) for b in self.base]
        self.scratch = np.empty(shape, self.dtype)
        a, b = _weights(int(np.prod(shape)))
        self.wa = [int(x) for x in a[:self.STAMP]]
        self.wb = [int(x) for x in b[:self.STAMP]]

    def _stamp(self, page: int, w: int):
        """Small whole numbers, exact in the served type."""
        digits = [page // 64, page % 64, w // 4096, (w // 64) % 64, w % 64,
                  (page * 7 + w) % 64]
        return np.asarray([d + 1 for d in digits], np.float32).astype(
            self.dtype)

    def block(self, page: int, w: int):
        """The bytes, in a buffer that the next call reuses (a fresh
        5 MiB array per page would fault its memory in each time)."""
        out = self.scratch
        np.copyto(out, self.base[(page * 7 + w) % N_BASE])
        out.reshape(-1)[:self.STAMP] = self._stamp(page, w)
        return out

    def checksum(self, page: int, w: int) -> tuple:
        base = self.base[(page * 7 + w) % N_BASE].reshape(-1)[:self.STAMP]
        old = base.view(np.uint16).astype(np.int64)
        new = self._stamp(page, w).view(np.uint16).astype(np.int64)
        sa, sb = self.base_sum[(page * 7 + w) % N_BASE]
        da = sum(x * (n - o) for x, n, o in zip(self.wa, new, old))
        db = sum(x * (n - o) for x, n, o in zip(self.wb, new, old))
        return (sa + da) % 2**32, (sb + db) % 2**32


class Driver:
    # the numbers `check` compares, each with a limit in the configuration
    CHECKS = ("pages_wrong", "ralt_score_err", "ralt_ticks_wrong")

    def __init__(self, cell, seed: int):
        from repro.tiering import KVTierConfig
        self.cell, self.seed = cell, seed
        self.spec = s = cell.family.spec(cell.config)
        k = cell.config["kv_tier"]
        self.kvcfg = KVTierConfig(
            n_pages=k["n_pages"], fast_slots=k["fast_slots"],
            page_tokens=k["page_tokens"], kv_heads=s.n_kv_heads,
            head_dim=s.head_dim, n_layers=s.n_layers, dtype=s.dtype,
            staging_slots=k["staging_slots"], sweep_every=k["sweep_every"])
        self.gen = load_module("traffic", cell.traffic["generator"],
                               cell.root)
        t = cell.traffic
        if t["sessions"] * t["pages_per_session"] != k["n_pages"]:
            raise ValueError("the sessions' pages must fill the cache's "
                             "n_pages")
        self.kv = None

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.tiering import TieredKVCache
        c = self.kvcfg
        self.kv = TieredKVCache(c)
        self.half = (c.n_layers, c.page_tokens, c.kv_heads, c.head_dim)
        self.contents = Contents((2, *self.half), c.dtype, self.seed)
        self.writes = np.zeros(c.n_pages, np.int64)
        self.log: list = []         # (pages, write counts at the read)
        self.rng = np.random.default_rng([self.seed, 5])
        for p in range(c.n_pages):
            self._write(p)
        self.stream = self.gen.Stream(self.cell.traffic)
        # every gather size the window can ask for, and the checksum
        count = len(self.stream.next_read()[1])
        self.stream = self.gen.Stream(self.cell.traffic)
        for n in range(1, count + 1):
            g = jnp.take(self.kv.fast_pool, jnp.zeros(n, jnp.int32), axis=0)
            jax.block_until_ready([g[i] for i in range(n)])
        np.asarray(self.kv.fast_pool[np.int64(0)])      # a demotion's read
        jax.block_until_ready(device_checksums(
            tuple(jnp.zeros((2, *self.half), c.dtype) for _ in range(count))))
        for _ in range(self.cell.traffic["warmup_reads"]):
            self._step(timed=None)
        jax.block_until_ready(self.kv.fast_pool)

    def _write(self, page: int):
        self.writes[page] += 1
        blob = self.contents.block(page, int(self.writes[page]))
        self.kv.write_page(page, blob[0], blob[1])

    def _step(self, timed):
        """One read (and the write that may follow it).  With `timed`, a
        dict of lists: latency, checksums, expected, tracker samples."""
        sess, pages, write = self.stream.next_read()
        if timed is None:
            jax.block_until_ready(self.kv.read_pages(pages))
        else:
            keep = self.rng.random() < TRACKER_SAMPLE or not timed["lat"]
            before = self.kv.tracker.state if keep else None
            expect = [int(self.writes[p]) for p in pages]
            with jax.profiler.TraceAnnotation(READ_SPAN):
                t = time.perf_counter()
                got = self.kv.read_pages(pages)
                jax.block_until_ready(got)
                timed["lat"].append(time.perf_counter() - t)
                timed["start"].append(t)
            if keep:
                timed["tracker"].append((before, self.kv.tracker.state,
                                         pages))
            if len(got) == len(pages):
                # taken before the next write: on the CPU backend a page
                # read from the host tier may share the host pool's memory
                timed["sums"].append(jax.block_until_ready(
                    device_checksums(tuple(got))))
            else:
                timed["sums"].append(None)
            self.log.append((pages, expect))
        if write:
            with jax.profiler.TraceAnnotation(WRITE_SPAN):
                t = time.perf_counter()
                self._write(self.stream.last_page(sess))
                jax.block_until_ready(self.kv.fast_pool)
                if timed is not None:
                    timed["write"].append(time.perf_counter() - t)

    # ------------------------------------------------------------ window
    COUNTERS = ("fast_hits", "slow_hits", "promoted", "demoted", "retained",
                "aborted", "sweeps", "flushes")

    def counters(self) -> dict:
        return {k: getattr(self.kv.clock, k) for k in self.COUNTERS}

    def window(self, seconds: float, stretch=None) -> dict:
        c0 = self.counters()
        self.timed = timed = {"lat": [], "start": [], "write": [],
                              "sums": [], "tracker": []}
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            if stretch is not None:
                stretch.open(t0)
            while time.perf_counter() - t0 < seconds:
                if stretch is not None:
                    stretch.poll()
                self._step(timed)
            t1 = time.perf_counter()
            if stretch is not None:
                stretch.close()
        self.t0, self.t1 = t0, t1
        c1 = self.counters()
        return self._record(t1 - t0, {k: c1[k] - c0[k] for k in c0},
                            range(len(timed["lat"])))

    def stretch_record(self, stretch) -> dict:
        """The reads started inside the traced stretch, and the change of
        the tier manager's counters across it."""
        c0, c1 = stretch.counters
        idx = [i for i, t in enumerate(self.timed["start"])
               if stretch.a <= t < stretch.b]
        return self._record(stretch.b - stretch.a,
                            {k: c1[k] - c0[k] for k in c0}, idx)

    def _record(self, seconds, counters, idx) -> dict:
        lat = [self.timed["lat"][i] for i in idx]
        counters["reads"] = len(lat)
        counters["pages_per_read"] = len(self.log[-1][0]) if self.log else 0
        counters["n_units"] = self.kvcfg.n_pages
        counters["page_bytes"] = self.contents.base[0].nbytes
        return {"seconds": seconds, "attempted": len(lat), "failed": 0,
                "samples": {"read_s": lat, "write_s": self.timed["write"]},
                "counters": counters}

    # ------------------------------------------------------------- check
    def release(self):
        """Keep what the check reads; free the pools."""
        t = self.timed
        t["sums"] = [None if s is None else np.asarray(s) for s in t["sums"]]
        t["tracker"] = [(jax.device_get(b), jax.device_get(a), pages)
                        for b, a, pages in t["tracker"]]
        self.alpha = self.kv.tracker.cfg.alpha
        self.kv = None
        import gc
        gc.collect()

    def _pages_wrong(self, sums) -> int:
        wrong = 0
        for (pages, expect), got in zip(self.log, sums):
            if got is None:
                wrong += len(pages)
                continue
            for p, w, g in zip(pages, expect, got):
                if tuple(int(x) for x in g) != self.contents.checksum(p, w):
                    wrong += 1
        return wrong

    def _ralt(self, dtype=np.float64):
        err, ticks_wrong = 0.0, 0
        n = self.kvcfg.n_pages
        for before, after, pages in self.timed["tracker"]:
            hits = np.zeros(n, bool)
            hits[list(pages)] = True
            now = int(after["now"])
            _, want = ralt_ref.ralt_update(before["tick"], before["score"],
                                           hits, now, self.alpha)
            got = after["score"]
            if dtype is not np.float64:
                _, got = ralt_ref.ralt_update(before["tick"],
                                              before["score"], hits, now,
                                              self.alpha, dtype=dtype)
            err = max(err, ralt_ref.score_error(got, want))
            ticks_wrong += int(np.sum(np.asarray(after["tick"]) != now))
            ticks_wrong += int(now < int(before["now"]))
        return err, ticks_wrong

    def check(self) -> dict:
        err, ticks_wrong = self._ralt()
        return {"pages_wrong": self._pages_wrong(self.timed["sums"]),
                "ralt_score_err": err, "ralt_ticks_wrong": ticks_wrong,
                "checked_reads": len(self.log),
                "checked_tracker_updates": len(self.timed["tracker"])}

    def control(self) -> dict:
        """The reference in the program's place, one precision down: a
        plain page store holding float8 pages, and the RALT update with
        bfloat16 scores."""
        import ml_dtypes
        sums = []
        for pages, expect in self.log[:CONTROL_READS]:
            low = [self.contents.block(p, w).astype(ml_dtypes.float8_e4m3fn)
                   .astype(self.contents.dtype) for p, w in zip(pages, expect)]
            sums.append(np.asarray(device_checksums(
                tuple(jnp.asarray(b) for b in low))))
        log, self.log = self.log, self.log[:CONTROL_READS]
        wrong = self._pages_wrong(sums)
        self.log = log
        err, _ = self._ralt(dtype=ml_dtypes.bfloat16)
        return {"pages_wrong": wrong, "ralt_score_err": err}
