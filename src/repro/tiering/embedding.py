"""Tiered embedding table: hot vocab rows resident in HBM.

The 128k–262k-vocab archs (llama3, minitron, gemma3, qwen3) have
multi-GiB embedding tables with Zipf-skewed row access — exactly the
paper's workload shape.  The full table lives in host memory (SD); a
fixed-size HBM row cache (FD) holds the hot rows, tracked by the RALT
tracker; misses are served from host (PCIe-charged) and staged; staged
rows are bulk-promoted when hot (promotion by flush — embedding rows
are read-only during serving, so the version checks of the KV path are
unnecessary; training updates invalidate via `invalidate_rows`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.serving import DEFAULT_SERVING_OBS
from .hotness import HotTracker, TrackerConfig
from .kvcache import HBM_BW, PCIE_BW, SimClock


class TieredEmbedding:
    # Obs plane: records while a profiler session does (repro.obs.serving).
    _obs = DEFAULT_SERVING_OBS
    _obs_track = "emb"

    def __init__(self, table: np.ndarray, fast_rows: int,
                 staging_slots: int = 256):
        self.table = table                       # host (V, d)
        V, d = table.shape
        self.fast_rows = fast_rows
        self.cache = jnp.zeros((fast_rows, d), table.dtype)
        self.row_of_slot = np.full(fast_rows, -1, np.int64)
        self.slot_of_row = np.full(V, -1, np.int64)
        self.free = list(range(fast_rows))[::-1]
        self.staging: set[int] = set()
        self.staging_slots = staging_slots
        self.row_bytes = d * table.dtype.itemsize
        self.tracker = HotTracker(TrackerConfig(
            n_units=V, unit_bytes=self.row_bytes,
            fast_bytes=fast_rows * self.row_bytes))
        self.tracker._obs_track = self._obs_track
        self.clock = SimClock()

    def lookup(self, token_ids) -> jnp.ndarray:
        """Exact gather (resident rows from HBM, misses from host)."""
        obs = self._obs
        rec = obs.poll()
        if rec:
            t0 = self.clock.total_s
            f0 = self.clock.flushes
        ids = np.asarray(token_ids).reshape(-1)
        slots = self.slot_of_row[ids]
        hit = slots >= 0
        out = np.empty((len(ids), self.table.shape[1]), self.table.dtype)
        if hit.any():
            got = jnp.take(self.cache, jnp.asarray(slots[hit]), axis=0)
            out[hit] = np.asarray(got)
            uniq = len(np.unique(ids[hit]))
            self.clock.hbm_s += uniq * self.row_bytes / HBM_BW
            self.clock.fast_hits += int(hit.sum())
        miss = ~hit
        if miss.any():
            rows = np.unique(ids[miss])
            out[miss] = self.table[ids[miss]]
            self.clock.pcie_s += len(rows) * self.row_bytes / PCIE_BW
            self.clock.slow_hits += int(miss.sum())
            self.staging.update(int(r) for r in rows)
        self.tracker.record_ids(jnp.asarray(np.unique(ids), jnp.int32))
        if len(self.staging) >= self.staging_slots:
            self.flush_promote()
        if rec and obs.attribution:
            obs.attr.observe("emb", self.clock.total_s - t0, len(ids),
                             int(miss.sum()), self.clock.flushes > f0)
        return jnp.asarray(out).reshape(*np.shape(token_ids), -1)

    def flush_promote(self):
        """Promotion by flush: hot staged rows -> HBM cache; cold
        resident rows are evicted to make room (retention keeps hot)."""
        obs, c = self._obs, self.clock
        if obs.enabled:
            obs.tracer.begin(self._obs_track, "emb/flush_promote",
                             {"staged": len(self.staging)})
            r0, p0 = c.retained, c.promoted
        self.tracker.refresh_limits()
        hot = self.tracker.hot()
        scores = np.asarray(self.tracker.scores())
        want = [r for r in self.staging if hot[r]]
        self.staging.clear()
        c.flushes += 1
        if not want:
            if obs.enabled:
                obs.tracer.end(self._obs_track, "emb/flush_promote",
                               {"promoted": 0})
            return
        # evict coldest residents if needed
        if len(self.free) < len(want):
            resident = [r for r in self.row_of_slot if r >= 0]
            resident.sort(key=lambda r: scores[r])
            for r in resident[:len(want) - len(self.free)]:
                if hot[r]:
                    self.clock.retained += 1    # retention: keep hot
                    continue
                s = self.slot_of_row[r]
                self.slot_of_row[r] = -1
                self.row_of_slot[s] = -1
                self.free.append(int(s))
                self.clock.demoted += 1
        new_slots, new_rows = [], []
        for r in want:
            if not self.free:
                break
            s = self.free.pop()
            new_slots.append(s)
            new_rows.append(r)
            self.slot_of_row[r] = s
            self.row_of_slot[s] = r
        if new_rows:
            self.cache = self.cache.at[jnp.asarray(new_slots)].set(
                jnp.asarray(self.table[new_rows]))
            self.clock.pcie_s += (len(new_rows) * self.row_bytes
                                  / PCIE_BW)
            self.clock.promoted += len(new_rows)
        if obs.enabled:
            tr, track = obs.tracer, self._obs_track
            if c.retained > r0:                       # retention pathway
                tr.instant(track, "page/retained",
                           {"pages": c.retained - r0})
            if c.promoted > p0:                       # promo-by-flush
                tr.instant(track, "page/promo_flush",
                           {"pages": c.promoted - p0})
            tr.end(track, "emb/flush_promote",
                   {"promoted": c.promoted - p0})
            obs.sample(self, track)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_obs", None)
        state.pop("_obs_track", None)
        return state

    def invalidate_rows(self, rows):
        for r in np.asarray(rows).reshape(-1):
            s = self.slot_of_row[r]
            if s >= 0:
                self.slot_of_row[r] = -1
                self.row_of_slot[s] = -1
                self.free.append(int(s))

    def fast_hit_rate(self):
        t = self.clock.fast_hits + self.clock.slow_hits
        return self.clock.fast_hits / t if t else 0.0
