"""Tiered paged KV cache — the paper's retention/promotion pathways on
the TPU memory hierarchy (HBM = FD, host DRAM = SD).

Pages (fixed tokens/page) live in either the HBM pool or the host pool;
a page table maps logical page -> (tier, slot).  The three pathways
(HotRAP §3.1) map as:

  * retention            — eviction sweeps (the FD->SD "compaction"
    analogue, run when the HBM pool is full) *skip hot pages*: only
    cold pages are demoted to host slots.
  * promotion by compaction — the same sweep checks the staging list of
    recently-accessed host pages in its range and copies the hot ones
    into freed HBM slots.
  * promotion by flush   — when the staging list reaches its capacity
    between sweeps (read-heavy phases with no evictions), hot staged
    pages are bulk-promoted immediately.

Correctness (paper §3.3/3.4 analogue): every page carries a version;
promotion records the version at stage time and aborts if the page was
appended/overwritten since (the "newer version shielded by a stale
promote" hazard).  The abort path is exercised in tests.

The device-side data plane (gathers, copies) is jax; the control plane
(page table, sweeps) is host Python — same split as an LSM-tree's
I/O vs. manifest logic.  `SimClock` charges HBM/PCIe time so benchmarks
report the paper-style simulated throughput on CPU.  The bytes the cache
really moves are plain counters on it: `h2d_bytes` and `d2h_bytes`
(host-to-device and device-to-host copies of pages), and
`pool_copy_bytes` (a non-donated `.at[s].set` writes a new pool: it
copies the whole HBM pool).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.serving import DEFAULT_SERVING_OBS
from .hotness import HotTracker, TrackerConfig

HBM_BW = 819e9      # v5e bytes/s
PCIE_BW = 16e9      # host<->device bytes/s (slow tier)


@dataclasses.dataclass(frozen=True)
class KVTierConfig:
    n_pages: int                 # logical pages
    fast_slots: int              # HBM pool capacity (pages)
    page_tokens: int = 16
    kv_heads: int = 8
    head_dim: int = 128
    n_layers: int = 1            # pages are per-layer-group blobs
    dtype: str = "bfloat16"
    staging_slots: int = 32      # promotion-by-flush trigger size
    sweep_every: int = 64        # accesses between eviction sweeps

    @property
    def page_bytes(self) -> int:
        return (2 * self.n_layers * self.page_tokens * self.kv_heads
                * self.head_dim * np.dtype(self.dtype).itemsize)


class SimClock:
    def __init__(self):
        self.hbm_s = 0.0
        self.pcie_s = 0.0
        self.fast_hits = 0
        self.slow_hits = 0
        self.promoted = 0
        self.demoted = 0
        self.retained = 0
        self.aborted = 0
        self.sweeps = 0         # maintenance passes (sweep/rebalance)
        self.flushes = 0        # bulk staging flushes

    @property
    def total_s(self):
        return self.hbm_s + self.pcie_s


class TieredKVCache:
    TIER_FAST, TIER_SLOW = 0, 1

    # Observability (repro.obs.serving): the class-level default plane
    # records while a profiler session does; one attribute check a site.
    _obs = DEFAULT_SERVING_OBS
    _obs_track = "kv"

    def __init__(self, cfg: KVTierConfig, tracker_cfg: TrackerConfig
                 | None = None, seed: int = 0):
        self.cfg = cfg
        dt = jnp.dtype(cfg.dtype)
        shape = (cfg.n_layers, cfg.page_tokens, cfg.kv_heads,
                 cfg.head_dim)
        self.fast_pool = jnp.zeros((cfg.fast_slots, 2, *shape), dt)
        # host pool: numpy (the "SD" tier)
        self.slow_pool = np.zeros((cfg.n_pages, 2, *shape),
                                  np.dtype(cfg.dtype))
        # page table (host): tier, slot, version
        self.tier = np.full(cfg.n_pages, self.TIER_SLOW, np.int8)
        self.slot_of = np.full(cfg.n_pages, -1, np.int64)
        self.version = np.zeros(cfg.n_pages, np.int64)
        self.free_slots = list(range(cfg.fast_slots))[::-1]
        self.page_of_slot = np.full(cfg.fast_slots, -1, np.int64)
        self.staging: dict[int, int] = {}     # page -> staged version
        self.tracker = HotTracker(tracker_cfg or TrackerConfig(
            n_units=cfg.n_pages, unit_bytes=cfg.page_bytes,
            fast_bytes=cfg.fast_slots * cfg.page_bytes))
        self.tracker._obs_track = self._obs_track
        self.clock = SimClock()
        self._access_count = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.pool_copy_bytes = 0

    def _moved(self, b0) -> dict:
        """Bytes moved since the counters read `b0` (a span's end args)."""
        return {"h2d_bytes": self.h2d_bytes - b0[0],
                "d2h_bytes": self.d2h_bytes - b0[1],
                "pool_copy_bytes": self.pool_copy_bytes - b0[2]}

    def _set_slot(self, s, data):
        """Copy one page from the host into HBM slot `s`."""
        self.fast_pool = self.fast_pool.at[s].set(
            jnp.asarray(data, self.fast_pool.dtype))
        self.h2d_bytes += self.cfg.page_bytes
        self.pool_copy_bytes += self.cfg.fast_slots * self.cfg.page_bytes

    # ------------------------------------------------------------------
    # data plane
    # ------------------------------------------------------------------
    def write_page(self, page: int, k, v):
        """Append/overwrite a page (prefill writes; bumps version)."""
        obs = self._obs
        rec = obs.poll()
        resident = self.tier[page] == self.TIER_FAST
        if rec:
            tr, track = obs.tracer, self._obs_track
            b0 = (self.h2d_bytes, self.d2h_bytes, self.pool_copy_bytes)
            tr.begin(track, "kv/write_page",
                     {"page": int(page), "tier": int(self.tier[page])})
            tr.begin(track, "kv/write_stage")
        self.version[page] += 1
        data = np.stack([np.asarray(k), np.asarray(v)])
        if resident:
            if rec:
                tr.swap(track, "kv/write_resident")
            self._set_slot(self.slot_of[page], data)
            self.clock.hbm_s += self.cfg.page_bytes / HBM_BW
        else:
            if rec:
                tr.swap(track, "kv/write_host")
            self.slow_pool[page] = data
            self.clock.pcie_s += self.cfg.page_bytes / PCIE_BW
        if rec:
            tr.end(track)
            tr.end(track, "kv/write_page", self._moved(b0))

    def read_pages(self, pages):
        """Gather pages for attention.  Fast pages: one device gather;
        slow pages: host fetch (PCIe-charged) + staged for promotion."""
        obs = self._obs
        rec = obs.poll()
        if rec:
            tr, track = obs.tracer, self._obs_track
            t0 = self.clock.total_s
            m0 = self.clock.sweeps + self.clock.flushes
            b0 = (self.h2d_bytes, self.d2h_bytes, self.pool_copy_bytes)
            tr.begin(track, "kv/read_pages",
                     {"read": self._access_count, "pages": len(pages)})
            tr.begin(track, "kv/lookup")
        pages = list(int(p) for p in pages)
        out = {}
        fast = [p for p in pages if self.tier[p] == self.TIER_FAST]
        slow = [p for p in pages if self.tier[p] == self.TIER_SLOW]
        if rec:
            tr.swap(track, "kv/gather")
        if fast:
            slots = jnp.asarray([self.slot_of[p] for p in fast])
            gathered = jnp.take(self.fast_pool, slots, axis=0)
            for i, p in enumerate(fast):
                out[p] = gathered[i]
            self.clock.hbm_s += len(fast) * self.cfg.page_bytes / HBM_BW
            self.clock.fast_hits += len(fast)
        if rec:
            tr.swap(track, "kv/host_fetch")
        for p in slow:
            out[p] = jnp.asarray(self.slow_pool[p])
            self.clock.pcie_s += self.cfg.page_bytes / PCIE_BW
            self.clock.slow_hits += 1
            # insert into the staging list (the mPC analogue) with the
            # version observed at read time (§3.3 check)
            self.staging.setdefault(p, int(self.version[p]))
        self.h2d_bytes += len(slow) * self.cfg.page_bytes
        if rec:
            tr.swap(track, "kv/track")
        self._record(pages)
        if rec:
            tr.end(track, "kv/track")
        self._maybe_flush()
        self._access_count += 1
        if self._access_count % self.cfg.sweep_every == 0:
            self.sweep()
        if rec:
            if obs.attribution:
                obs.attr.observe(
                    "kv", self.clock.total_s - t0, len(pages), len(slow),
                    self.clock.sweeps + self.clock.flushes > m0)
            tr.end(track, "kv/read_pages",
                   {"slow": len(slow), **self._moved(b0)})
        return [out[p] for p in pages]

    # ------------------------------------------------------------------
    # hotness plumbing
    # ------------------------------------------------------------------
    def _record(self, pages):
        self.tracker.record_ids(jnp.asarray(pages, jnp.int32))

    def _hot_set(self):
        self.tracker.refresh_limits()
        return self.tracker.hot()

    # ------------------------------------------------------------------
    # pathways
    # ------------------------------------------------------------------
    def _promote(self, page: int, staged_version: int, hot: bool):
        """Copy page host->HBM if hot, version unchanged, space found,
        and the hot-set size limit (Alg. 1 auto-tuned) has headroom —
        under uniform access the limit collapses to L_hs and promotion
        traffic goes to ~zero (the paper's <1% uniform overhead)."""
        if not hot:
            self.staging.pop(page, None)
            return False
        if self.version[page] != staged_version:      # §3.3/3.4 hazard
            self.clock.aborted += 1
            self.staging.pop(page, None)
            if self._obs.enabled:
                self._obs.tracer.instant(
                    self._obs_track, "page/promo_abort",
                    {"page": int(page),
                     "staged_version": int(staged_version),
                     "version": int(self.version[page])})
            return False
        occupied = self.cfg.fast_slots - len(self.free_slots)
        hot_limit = float(self.tracker.state["hot_limit"])
        if (occupied + 1) * self.cfg.page_bytes > hot_limit:
            return False                              # hot-set cap
        if not self.free_slots:
            return False                              # retry next sweep
        s = self.free_slots.pop()
        self._set_slot(s, self.slow_pool[page])
        self.tier[page] = self.TIER_FAST
        self.slot_of[page] = s
        self.page_of_slot[s] = page
        self.clock.pcie_s += self.cfg.page_bytes / PCIE_BW
        self.clock.promoted += 1
        self.staging.pop(page, None)
        return True

    def _demote(self, page: int):
        s = self.slot_of[page]
        self.slow_pool[page] = np.asarray(self.fast_pool[s])
        self.d2h_bytes += self.cfg.page_bytes
        self.tier[page] = self.TIER_SLOW
        self.slot_of[page] = -1
        self.page_of_slot[s] = -1
        self.free_slots.append(int(s))
        self.clock.pcie_s += self.cfg.page_bytes / PCIE_BW
        self.clock.demoted += 1

    def sweep(self):
        """Scheduled maintenance (the compaction analogue): demote cold
        resident pages (retention skips hot ones), then promote hot
        staged pages into the freed slots (promotion by compaction)."""
        obs, c = self._obs, self.clock
        if obs.enabled:
            obs.tracer.begin(
                self._obs_track, "kv/sweep",
                {"resident": int((self.page_of_slot >= 0).sum()),
                 "staged": len(self.staging)})
            r0, d0, p0, a0 = c.retained, c.demoted, c.promoted, c.aborted
        hot = self._hot_set()
        resident = [int(p) for p in self.page_of_slot if p >= 0]
        for p in resident:
            if hot[p]:
                self.clock.retained += 1              # retention
            elif len(self.free_slots) < max(self.cfg.fast_slots // 4, 1):
                self._demote(p)
        for p, ver in list(self.staging.items()):
            self._promote(p, ver, bool(hot[p]))
        c.sweeps += 1
        if obs.enabled:
            tr, track = obs.tracer, self._obs_track
            if c.retained > r0:                       # retention pathway
                tr.instant(track, "page/retained",
                           {"pages": c.retained - r0})
            if c.promoted > p0:                       # promo-by-compaction
                tr.instant(track, "page/promo_compaction",
                           {"pages": c.promoted - p0})
            tr.end(track, "kv/sweep",
                   {"demoted": c.demoted - d0, "promoted": c.promoted - p0,
                    "aborted": c.aborted - a0})
            obs.sample(self, track)

    def _maybe_flush(self):
        """Promotion by flush: staging full between sweeps."""
        if len(self.staging) < self.cfg.staging_slots:
            return
        obs, c = self._obs, self.clock
        if obs.enabled:
            obs.tracer.begin(self._obs_track, "kv/staging_flush",
                             {"staged": len(self.staging)})
            p0, a0 = c.promoted, c.aborted
        hot = self._hot_set()
        for p, ver in list(self.staging.items()):
            self._promote(p, ver, bool(hot[p]))
        # cold staged pages are dropped (paper: cold immPC records)
        self.staging.clear()
        c.flushes += 1
        if obs.enabled:
            if c.promoted > p0:                       # promo-by-flush
                obs.tracer.instant(self._obs_track, "page/promo_flush",
                                   {"pages": c.promoted - p0})
            obs.tracer.end(self._obs_track, "kv/staging_flush",
                           {"promoted": c.promoted - p0,
                            "aborted": c.aborted - a0})
            obs.sample(self, self._obs_track)

    # ------------------------------------------------------------------
    def __getstate__(self):
        """Pickle without the obs plane (it holds closures/clock refs);
        the class-level NULL plane reasserts itself on load."""
        state = dict(self.__dict__)
        state.pop("_obs", None)
        state.pop("_obs_track", None)
        return state

    # ------------------------------------------------------------------
    def fast_hit_rate(self):
        t = self.clock.fast_hits + self.clock.slow_hits
        return self.clock.fast_hits / t if t else 0.0
